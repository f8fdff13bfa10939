"""Family module of dense GQA decoders (the Llama architecture).

A configuration names this module under ``"reference"``; the harness takes
from it everything that depends on the architecture, as ``harness.spec``
lists it: the sizes (``Dims``), the program's ``ArchConfig`` arguments,
the parameter tree (one group of identical layers), the work of a decode
step, and the plain float32 reference.

The plain reference follows the published description of both
configurations' ``config.json`` (``LlamaForCausalLM``): pre-norm RMSNorm
``x * rsqrt(mean(x^2) + eps) * w``, rotary embedding on the two halves of
each head (``rotate_half``, inverse frequencies ``theta^(-2i/d)``),
grouped-query attention in which query head ``h`` reads key/value head
``h // (heads / kv_heads)``, causal softmax scaled by ``1/sqrt(head_dim)``,
a SwiGLU MLP ``(silu(x Wg) * (x Wu)) Wd``, a final RMSNorm and an output
head (the embedding's transpose when tied). No cache, no batching of
requests into slots, no kernels: one full forward pass over each sequence,
layer by layer, so that the largest model fits after the program's state is
freed.

It imports nothing of the program under test. Its weights come from the
benchmark's own seeded generator, upcast from the served dtype to float32.
Matrix products run at ``highest`` precision, which on a TPU keeps float32.

``quant="fp8"`` is the control: every operand of every projection and of
the head is rounded to float8 e4m3 with one scale per row of activations
and one per output column of weights, the step below bfloat16 that a
later change might be tempted to take.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


# -- sizes ----------------------------------------------------------------


@dataclass(frozen=True)
class Dims:
    """The sizes of a dense GQA decoder as its configuration file states
    them (Hugging Face ``config.json`` keys), plus the engine shape."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    norm_eps: float
    dtype: str
    max_batch: int
    max_seq: int
    kv_block_size: int

    @property
    def family(self):
        """This module: the harness reaches the family through its sizes."""
        return sys.modules[type(self).__module__]

    @property
    def padded_vocab(self) -> int:
        """Rows of the embedding table: the vocab rounded up to 256."""
        return -(-self.vocab // 256) * 256

    @property
    def dtype_bytes(self) -> int:
        return {"bfloat16": 2, "float16": 2, "float32": 4}[self.dtype]


def dims(config: Dict[str, Any]) -> Dims:
    """``Dims`` of a configuration file's contents."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    eng = config["engine"]
    return Dims(
        layers=config["num_hidden_layers"], d_model=D, heads=H,
        kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or D // H,
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        tied=bool(config["tie_word_embeddings"]),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        dtype=config["torch_dtype"], max_batch=eng["max_batch"],
        max_seq=eng["max_seq"], kv_block_size=eng["kv_block_size"])


def program_config(name: str, d: Dims) -> Dict[str, Any]:
    """Keyword arguments of the program's ``ArchConfig`` for ``d``."""
    return dict(
        name=name, family="dense", num_layers=d.layers, d_model=d.d_model,
        num_heads=d.heads, num_kv_heads=d.kv_heads, head_dim=d.head_dim,
        d_ff=d.d_ff, vocab_size=d.vocab, tie_embeddings=d.tied,
        rope_theta=d.rope_theta, norm_eps=d.norm_eps, param_dtype=d.dtype)


# -- parameter tree -------------------------------------------------------


def layer_shapes(d: Dims) -> dict:
    D, F = d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    return {"ln_attn": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
            "wo": (q, D), "ln_mlp": (D,), "w_gate": (D, F), "w_up": (D, F),
            "w_down": (F, D)}


def layer_groups(d: Dims) -> list:
    """Every layer alike: one group stacked under ``"layers"``."""
    return [("layers", d.layers, layer_shapes(d))]


def top_shapes(d: Dims) -> dict:
    out = {"embed": (d.padded_vocab, d.d_model), "final_norm": (d.d_model,)}
    if not d.tied:
        out["lm_head"] = (d.d_model, d.padded_vocab)
    return out


# -- work of a decode step ------------------------------------------------


def matmul_params(d: Dims) -> int:
    """Weights one row multiplies by: every layer's projections and the
    output head (the embedding lookup is a gather, not a product)."""
    D, F = d.d_model, d.d_ff
    q, kv = d.heads * d.head_dim, d.kv_heads * d.head_dim
    per_layer = D * q + 2 * D * kv + q * D + 3 * D * F
    return d.layers * per_layer + D * d.vocab


def param_bytes(d: Dims) -> int:
    """Bytes of weights one step reads: every layer, the final norm and the
    output head. With an untied head the embedding table is read only at
    the rows' tokens, which ``step_bytes`` adds."""
    D = d.d_model
    per_layer = matmul_params(d) - D * d.vocab
    norms = d.layers * 2 * D + D
    return (per_layer + norms + D * d.vocab) * d.dtype_bytes


def kv_bytes_per_position(d: Dims) -> int:
    return d.layers * 2 * d.kv_heads * d.head_dim * d.dtype_bytes


def step_flops(d: Dims, rows: int, ctx: int) -> int:
    """2 x weights per row, plus QK^T and PV over the live positions."""
    attn = 4 * d.layers * d.heads * d.head_dim * ctx
    return 2 * rows * matmul_params(d) + attn


def step_bytes(d: Dims, rows: int, ctx: int) -> int:
    """Weights once, the K/V of the live positions (the new position is
    written and read), and the embedding rows of an untied table."""
    gather = 0 if d.tied else rows * d.d_model * d.dtype_bytes
    return param_bytes(d) + kv_bytes_per_position(d) * ctx + gather


# -- the plain reference --------------------------------------------------

PAD = 128  # sequences are padded to a multiple of this (causal: harmless)
F8_MAX = 448.0  # largest finite float8 e4m3fn


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [N, S, heads, dh]; the first half pairs with the second."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, :, None].astype(jnp.float32) * inv  # [N, S, dh/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    half = dh // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _block(x, w, dims, quant):
    """One decoder layer over x [N, S, D] (positions 0..S-1)."""
    N, S, D = x.shape
    H, Hkv, dh = dims.heads, dims.kv_heads, dims.head_dim
    pos = jnp.broadcast_to(jnp.arange(S)[None], (N, S))
    h = _rms(x, w["ln_attn"], dims.norm_eps)
    q = _rope(_mm(h, w["wq"], quant).reshape(N, S, H, dh), pos, dims.rope_theta)
    k = _rope(_mm(h, w["wk"], quant).reshape(N, S, Hkv, dh), pos,
              dims.rope_theta)
    v = _mm(h, w["wv"], quant).reshape(N, S, Hkv, dh)
    rep = H // Hkv
    k = jnp.repeat(k, rep, axis=2)  # query head h reads kv head h // rep
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(N, S, H * dh)
    x = x + _mm(a, w["wo"], quant)
    h = _rms(x, w["ln_mlp"], dims.norm_eps)
    g = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(g, w["w_down"], quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def logits(dims, layer: Callable[[int], dict], top: dict,
           seqs: List[List[int]], quant: Optional[str] = None,
           group_elems: int = 1 << 28):
    """Reference logits at every position of each sequence.

    ``layer(i)`` returns layer ``i``'s leaves, ``top`` the embedding, final
    norm and head. Returns one float32 device array [S, vocab] per
    sequence, S its length rounded up to ``PAD``; row ``p`` predicts token
    ``p + 1`` (rows past the sequence's end are padding). Sequences of one
    padded length run as a batch of at most ``group_elems`` attention
    scores.
    """
    with jax.default_matmul_precision("highest"):
        return _logits(dims, layer, _f32(top), seqs, quant, group_elems)


def _logits(dims, layer, top, seqs, quant, group_elems):
    block = jax.jit(lambda x, w: _block(x, w, dims, quant))
    groups = {}
    for i, s in enumerate(seqs):
        groups.setdefault(-(-len(s) // PAD) * PAD, []).append(i)
    batches = []
    for S, idx in sorted(groups.items()):
        per = max(1, group_elems // (dims.heads * S * S))
        batches += [(S, idx[j:j + per]) for j in range(0, len(idx), per)]
    xs = []
    for S, idx in batches:
        tok = np.zeros((len(idx), S), np.int32)
        for r, i in enumerate(idx):
            tok[r, :len(seqs[i])] = seqs[i]
        xs.append(top["embed"][jnp.asarray(tok)])
    for li in range(dims.layers):
        w = _f32(layer(li))
        xs = [block(x, w) for x in xs]
        del w
    head = (top["embed"].T if dims.tied else top["lm_head"])[:, :dims.vocab]
    # the norm and the head are arguments: closed over, they would be
    # folded into every compiled program as constants of a gigabyte
    final = jax.jit(lambda x, norm, head: _mm(_rms(x, norm, dims.norm_eps),
                                              head, quant))
    out = [None] * len(seqs)
    for (S, idx), x in zip(batches, xs):
        for r, i in enumerate(idx):
            out[i] = final(x[r], top["final_norm"], head)
    return out
