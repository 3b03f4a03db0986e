"""The dense pre-norm decoder: RMSNorm, rotary embeddings on the split
halves, optional q/k RMSNorm, grouped-query causal attention, SwiGLU, a
tied or separate head. Configurations that name ``"reference":
"dense_decoder"`` are read by this module: its sizes (``model_spec``),
its plain float32 reference (``forward``), the plan of its weights
(``CONTRACT``, ``ONES``, ``EMBED``) and its logical work
(``train_flops``).

Every projection matmul of the reference is a TimeFloats E4M4 scalar
product (``reference.tf_matmul``); everything else runs in float32 at
``highest`` matmul precision. Parameters are read from the program's
parameter layout (stacked layers under ``groups[0]['params']``), as one
would read a checkpoint.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.chip.reference import MAN_BITS, rms_norm, rope, tf_matmul
from benchmarks.chip.work import causal_pairs

# The weights plan. Leaf name -> the dims it contracts, counted after the
# layer dim of a stacked leaf: N(0, 1/fan_in) over their product.
CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w_gate": (0,), "w_up": (0,), "w_down": (0,), "lm_head": (0,)}
ONES = ("scale", "q_norm", "k_norm")   # norms: ones
EMBED = ("embed",)                     # token tables: N(0, 0.02^2)


def model_spec(config: dict) -> dict:
    """The sizes the reference needs, from the configuration file."""
    return {"n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rope_theta": float(config["rope_theta"]),
            "rms_norm_eps": float(config["rms_norm_eps"]),
            "sliding_window": config.get("sliding_window")}


def forward(params, tokens, real, spec: dict, man_bits: int = MAN_BITS,
            remat: bool = False):
    """Logits (B, S, V) of ``tokens (B, S)``. ``real (B, S)`` is 1 at real
    positions and 0 at padding: padding is kept out of every operand's
    prescale and, being at the end of a row, out of every real position's
    attention. Every projection takes all B * S rows as one operand, as
    the program's does."""
    b, s_len = tokens.shape
    keep = real[..., None].astype(jnp.float32)
    lin = lambda a, w: tf_matmul(a * keep, w.reshape(w.shape[0], -1),
                                 man_bits)
    h_q, h_kv = spec["n_heads"], spec["n_kv_heads"]
    hd = spec["head_dim"]
    eps = spec["rms_norm_eps"]
    pos = jnp.arange(s_len)
    causal = pos[None, :] <= pos[:, None]
    if spec.get("sliding_window"):
        causal &= pos[:, None] - pos[None, :] < spec["sliding_window"]
    lp = params["groups"][0]["params"]

    def layer(x, p):
        h = rms_norm(x, p["norm1"]["scale"], eps)
        a = p["mixer"]
        q = lin(h, a["wq"]).reshape(b, s_len, h_q, hd)
        k = lin(h, a["wk"]).reshape(b, s_len, h_kv, hd)
        v = lin(h, a["wv"]).reshape(b, s_len, h_kv, hd)
        if "q_norm" in a:
            q = rms_norm(q, a["q_norm"], eps)
            k = rms_norm(k, a["k_norm"], eps)
        q = rope(q, pos, spec["rope_theta"])
        k = rope(k, pos, spec["rope_theta"])
        g = h_q // h_kv
        qg = q.reshape(b, s_len, h_kv, g, hd)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", pr, v,
                       precision=jax.lax.Precision.HIGHEST)
        o = o.reshape(b, s_len, h_q * hd)
        x = x + lin(o, a["wo"].reshape(h_q * hd, -1))
        h2 = rms_norm(x, p["norm2"]["scale"], eps)
        f = p["ffn"]
        u = jax.nn.silu(lin(h2, f["w_gate"])) * lin(h2, f["w_up"])
        x = x + lin(u, f["w_down"])
        return x, None

    if remat:
        layer = jax.checkpoint(layer)
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, lp)
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    head = (params["embed"].T if "lm_head" not in params
            else params["lm_head"])
    return tf_matmul(x * keep, head, man_bits)


def _dims(c: dict):
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    return c["num_hidden_layers"], d, f, h, kv, hd, c["vocab_size"]


def layer_matmul_params(c: dict) -> int:
    _, d, f, h, kv, hd, _ = _dims(c)
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def forward_flops(c: dict, tokens: int, attended: int,
                  head_tokens: int) -> float:
    """Forward operations for ``tokens`` positions that together attend to
    ``attended`` (position, key) pairs, with the head applied at
    ``head_tokens`` of them."""
    n_layers, d, _, h, _, hd, vocab = _dims(c)
    mm = 2.0 * tokens * n_layers * layer_matmul_params(c)
    att = 4.0 * n_layers * h * hd * attended
    return mm + att + 2.0 * head_tokens * d * vocab


def train_flops(c: dict, batch: int, seq: int) -> float:
    """Forward and backward (three forwards' worth) of one step."""
    return 3.0 * forward_flops(c, batch * seq, batch * causal_pairs(seq),
                               batch * seq)
