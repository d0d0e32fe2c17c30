"""Checker configuration: which files are hot paths, which host-boundary
calls are blessed, where the RNG discipline applies.

:func:`default_config` encodes THIS repo's invariants — the serving decode
loop, the counter-RNG scheme, the kernels contract.  Tests build ad-hoc
configs pointing at fixture trees, so nothing in :mod:`repro.analysis.core`
or the rules may assume the defaults.

Qualname globs match the dotted names :meth:`FileContext.qualname` builds
(``ServeEngine._sample_rows``, ``rewrap_peft.rec.init_one``); path globs
match root-relative posix paths.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

__all__ = ["AnalysisConfig", "default_config"]

PathGlobs = Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class AnalysisConfig:
    #: repo root every path is relative to
    root: str
    #: files parsed into the project index (targets must fall inside)
    index_globs: PathGlobs = ("src/**/*.py", "benchmarks/**/*.py",
                              "tests/test_*.py")

    # -- HOSTSYNC ----------------------------------------------------------
    #: path -> qualname globs of host-side functions on the decode hot path
    #: (jit-decorated / jax.jit()-wrapped functions are always checked)
    hostsync_hot: Dict[str, PathGlobs] = dataclasses.field(
        default_factory=dict)
    #: (path glob, qualname glob, call key) triples naming the blessed
    #: host-boundary transfers, e.g. the engine's post-sample device_get
    hostsync_allow: Tuple[Tuple[str, str, str], ...] = ()

    # -- RNG-DISCIPLINE ----------------------------------------------------
    #: where the discipline applies at all (library code, not benches/tests)
    rng_scope: PathGlobs = ()
    #: (path glob, qualname glob) pairs allowed to mint/split keys
    rng_allow: Tuple[Tuple[str, str], ...] = ()

    # -- OBS-GATE ----------------------------------------------------------
    #: path -> qualname globs of functions whose tracker calls must be
    #: gated behind ``_obs`` / ``is_noop`` checks
    obsgate_hot: Dict[str, PathGlobs] = dataclasses.field(
        default_factory=dict)

    # -- PALLAS-CONTRACT ---------------------------------------------------
    #: directory of kernel modules (each must pair with ref.py + ops.py)
    kernels_dir: str = "src/repro/kernels"
    #: kernel-dir files that are not kernel modules themselves
    kernels_exclude: PathGlobs = ("__init__.py", "ops.py", "ref.py")
    #: where tests live, for the oracle/wrapper pairing check
    test_globs: PathGlobs = ("tests/test_*.py",)

    # -- DEPRECATION -------------------------------------------------------
    #: files whose DeprecationWarning shims must be test-covered
    deprecation_scope: PathGlobs = ("src/**", "benchmarks/**")


_ENGINE = "src/repro/serve/engine.py"


def default_config(root: str) -> AnalysisConfig:
    """The repo's own invariant map.

    Hot-path sets mirror the runtime pins they replace: the OBS-GATE list
    is the engine step's call graph — decode, admission, chunk
    continuation, finish — that ``bench_serve``'s NoopTracker counter and
    ``tests/test_obs.py`` guard; the HOSTSYNC allowlist is the
    engine's one sanctioned host boundary — the post-sample token
    materialization, which PR 9 consolidated into single ``jax.device_get``
    batched transfers."""
    return AnalysisConfig(
        root=root,
        hostsync_hot={
            _ENGINE: ("*._sample_rows", "*._spec_group", "*._spec_step",
                      "*._decode_live"),
        },
        hostsync_allow=(
            (_ENGINE, "*._sample_rows", "jax.device_get"),
            (_ENGINE, "*._spec_group", "jax.device_get"),
        ),
        rng_scope=("src/repro/**",),
        rng_allow=(
            # the counter scheme itself: every sampling draw is
            # fold_in(PRNGKey(seed), n_generated) in serve/sampling.py
            ("src/repro/serve/sampling.py", "*"),
            # parameter init trees (keys split once, before any serving)
            ("src/repro/models/*.py", "*init*"),
            ("src/repro/models/model.py", "abstract_params"),
            # the launch path mints the root key from the config seed
            ("src/repro/launch/*.py", "*"),
            ("src/repro/train/trainer.py", "state_shardings"),
        ),
        obsgate_hot={
            _ENGINE: ("*.run_stream", "*._decode_step", "*._decode_live",
                      "*._spec_step", "*._spec_group", "*._sample_rows",
                      "*._ensure_decode_pages", "*._suspend",
                      "*._finish_slot", "*._admit*", "*._try_admit_pages",
                      "*._run_prefill_groups", "*._continue_prefills",
                      "*._record_admissions", "*._finish_chunks",
                      "*._finish_admitted", "*._span", "*._stamp_*",
                      "*._observe_compiles"),
            "src/repro/serve/scheduler.py": ("*.push", "*.window"),
        },
    )
