"""With the timed path broken underneath, ``correct`` comes out false: a
token altered where the sampler produces it.  The harness's look for a chip
is skipped; everything else is a whole serving run at the ``tiny`` size."""
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def bench_dir(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


def test_altered_token(bench_dir, monkeypatch):
    from repro.serve import sampling
    real = sampling.sample_tokens

    def altered(logits, *args, **kwargs):
        toks, *rest = real(logits, *args, **kwargs)
        return ((toks + 1) % logits.shape[-1], *rest)

    monkeypatch.setattr(sampling, "sample_tokens", altered)
    correct, checks = tiny.correct(bench_dir, tiny.SERVE)
    assert not correct, checks
