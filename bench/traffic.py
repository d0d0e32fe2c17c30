"""Traffic from a mix file and a seed.

Every seed of a mix gets the same work.  Prompt lengths, output lengths,
gaps between arrivals and requests per tenant are drawn at fixed quantiles
of the mix's distributions, and put in one order by the mix's own
``population_seed``; the run's seed picks every token.  Near the knee the
order is part of the work: which long prompts land in a burst moved the
95th percentile of time to first token from 1.6 s to 3.9 s over six seeds
on one v5e, while two runs of one seed agreed within 15%.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_N01 = NormalDist()


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def lognormal_grid(spec: Dict, n: int) -> np.ndarray:
    """``n`` whole numbers at the midpoint quantiles of a log-normal with the
    given median and sigma, clipped to [min, max]."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([_N01.inv_cdf(float(p)) for p in q])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class ServeRequest:
    uid: int
    due_s: float          # seconds after the window opens
    tenant: int           # 0 is the base model's users
    prompt: np.ndarray    # int32, opening with the tenant's system prompt
    max_new_tokens: int


def _zipf_counts(n: int, tenants: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, tenants + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - counts.sum()
    counts[np.argsort(exact - counts)[::-1][:rest]] += 1
    return counts


def _burst_times(unit: np.ndarray, mix: Dict) -> np.ndarray:
    """Map unit-rate arrival times through the inverse of the cumulative
    rate: ``burst_factor`` times the base rate for the first
    ``burst_share`` of every ``burst_period_s``, a mean of ``rate_rps``."""
    rate = mix["rate_rps"]
    f, share, period = (mix["burst_factor"], mix["burst_share"],
                        mix["burst_period_s"])
    low = rate / (1 - share + f * share)
    per_period = low * period * (1 - share + f * share)
    hi_part = low * f * share * period
    out = np.empty_like(unit)
    for i, u in enumerate(unit):
        k, rem = divmod(u, per_period)
        if rem < hi_part:
            t = rem / (low * f)
        else:
            t = share * period + (rem - hi_part) / low
        out[i] = k * period + t
    return out


def system_prompts(mix: Dict, seed: int, vocab: int) -> np.ndarray:
    """Each tenant's system prompt, (tenants, system_prompt_tokens)."""
    return rng(seed, 7).integers(1, vocab, (mix["tenants"],
                                            mix["system_prompt_tokens"]))


def serve_requests(mix: Dict, seed: int, seconds: float,
                   vocab: int) -> List[ServeRequest]:
    """The open-loop requests due in a window of ``seconds``: exactly
    ``round(rate_rps * seconds)`` of them, the last due just before the
    window closes."""
    n = max(1, int(round(mix["rate_rps"] * seconds)))
    g = rng(seed, 100)
    order = rng(mix["population_seed"], 100)
    tenants = mix["tenants"]
    system = system_prompts(mix, seed, vocab)
    users = order.permutation(lognormal_grid(mix["user_tokens"], n))
    outs = order.permutation(lognormal_grid(mix["output_tokens"], n))
    who = order.permutation(np.repeat(
        np.arange(tenants), _zipf_counts(n, tenants, mix["zipf_s"])))
    # unit-rate gaps at the quantiles of Exp(1), scaled so the n-th arrival
    # falls half a mean gap before the window closes
    gaps = order.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    unit = np.cumsum(gaps)
    total = mix["rate_rps"] * seconds * (n - 0.5) / n
    due = _burst_times(unit * total / unit[-1], mix)
    out = []
    for i in range(n):
        user = g.integers(1, vocab, int(users[i]), dtype=np.int64)
        prompt = np.concatenate([system[who[i]], user]).astype(np.int32)
        out.append(ServeRequest(uid=i, due_s=float(due[i]),
                                tenant=int(who[i]), prompt=prompt,
                                max_new_tokens=int(outs[i])))
    return out


def packed_batch(mix: Dict, seed: int, index: int, vocab: int) -> Dict:
    """Batch ``index`` of a packed-document job: documents of log-normal
    length, separated by ``eos_id``, packed into rows of ``seq_len``;
    labels are the next token, -1 past the row's end."""
    b, s = mix["batch"], mix["seq_len"]
    g = rng(seed, 1_000_000 + index)
    need = b * s + 1
    grid = lognormal_grid(mix["doc_tokens"], 64)
    lengths = []
    while sum(lengths) + len(lengths) < need:
        lengths.extend(g.permutation(grid).tolist())
    stream = []
    for ln in lengths:
        stream.append(g.integers(1, vocab, int(ln), dtype=np.int64))
        stream.append(np.array([mix["eos_id"]]))
    flat = np.concatenate(stream)[:need]
    rows = flat[:b * s].reshape(b, s).astype(np.int32)
    labels = flat[1:b * s + 1].reshape(b, s).astype(np.int32)
    labels[:, -1] = -1
    return {"tokens": rows, "labels": labels}


def tenant_adapter(tenant: int, adapters: int) -> int:
    """Bank column of a tenant: tenants 1..adapters have their own adapter,
    tenant 0 and any beyond the adapters use the base model."""
    return tenant if 1 <= tenant <= adapters else 0
