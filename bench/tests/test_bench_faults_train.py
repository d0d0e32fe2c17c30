"""With the timed path broken underneath, ``correct`` comes out false: a
train step that returns its state unchanged, and one that leaves out half
of the batch and takes the mean over the rest.  The harness's look for a
chip is skipped; everything else is a whole training run at the ``tiny``
size."""
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


def _broken_step(monkeypatch, fault):
    from repro.train import trainer
    real = trainer.make_train_step

    def make(cfg, tc, **kwargs):
        step = real(cfg, tc, **kwargs)

        def broken(state, batch):
            if fault == "unchanged":
                return state, step(state, batch)[1]
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(state, half)
        return broken

    monkeypatch.setattr(trainer, "make_train_step", make)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_train_step(bench_dir, monkeypatch, fault):
    _broken_step(monkeypatch, fault)
    correct, checks = tiny.correct(bench_dir, tiny.TRAIN)
    assert not correct, checks
