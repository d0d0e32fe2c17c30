"""Model arithmetic of a dense decoder: the operations and bytes one token,
step or sequence needs, from the configuration's sizes alone.

``m`` is the dict of sizes that ``model_sizes`` makes from a configuration
file.  Attention counts the causal work each token needs (its own key and
every earlier one).  Training counts the forward pass and the gradient with
respect to the inputs, since frozen weights need no weight gradient; work
that rematerialization repeats is not counted.
"""
from __future__ import annotations

from typing import Dict, Iterable


def model_sizes(config: Dict) -> Dict:
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    hd = config.get("head_dim", d // h)
    return {"d": d, "ff": config["intermediate_size"], "heads": h,
            "kv_heads": config["num_key_value_heads"], "head_dim": hd,
            "layers": config["num_hidden_layers"],
            "vocab": config["vocab_size"], "rank": config["peft"]["rank"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
            "neumann_terms": config["peft"]["neumann_terms"]}


def layer_params(m: Dict) -> int:
    """Weights of one layer's linears (q, k, v, o, gate, up, down)."""
    d, q, kv = m["d"], m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * m["ff"]


def head_params(m: Dict) -> int:
    return m["d"] * m["vocab"]


def attn_flops(m: Dict, context: int) -> float:
    """One token attending over ``context`` keys, in every layer."""
    return 4.0 * m["layers"] * m["heads"] * m["head_dim"] * context


def decode_flops(m: Dict, contexts: Iterable[int]) -> float:
    """One decode step: a token per live row, each with its context."""
    contexts = list(contexts)
    per_tok = 2.0 * (m["layers"] * layer_params(m) + head_params(m))
    return per_tok * len(contexts) + sum(attn_flops(m, c) for c in contexts)


def bank_bytes(m: Dict, itemsize: int) -> int:
    """One adapter's rank-r factors over every linear of every layer."""
    d, q, kv = m["d"], m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    dims = [(d, q), (d, kv), (d, kv), (q, d), (d, m["ff"]), (d, m["ff"]),
            (m["ff"], d)]
    return m["layers"] * sum((a + b) * m["rank"] for a, b in dims) * itemsize


def decode_bytes(m: Dict, contexts: Iterable[int], adapters: int,
                 weight_itemsize: int, bank_itemsize: int,
                 kv_itemsize: int) -> float:
    """One decode step: every weight once, the factors of each adapter the
    live rows use, and each row's live keys and values (plus the new ones
    written)."""
    kv_tok = 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * kv_itemsize
    contexts = list(contexts)
    return float((m["layers"] * layer_params(m) + head_params(m))
                 * weight_itemsize + adapters * bank_bytes(m, bank_itemsize)
                 + kv_tok * (sum(contexts) + len(contexts)))


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward plus input gradient of the linears (2 + 2 per weight), and of
    causal attention (forward, and twice that backward) at sequence length
    ``seq``."""
    linear = 4.0 * (m["layers"] * layer_params(m) + head_params(m))
    attn = 3 * 2.0 * m["layers"] * m["heads"] * m["head_dim"] * (seq + 1)
    return linear + attn
