"""Model assembly: one generic implementation per family, driven by ArchConfig.

Public surface:
    m = Model(cfg, ctx)
    params   = m.init(rng)
    logits   = m.forward(params, batch)                  # train / full forward
    out, kv  = m.prefill(params, batch)                  # fill caches
    cache    = m.init_cache(batch_size, max_seq)
    cache, logits = m.decode_step(params, cache, tokens) # one token
    m.param_logical_axes() / m.param_shapes() / m.input_specs(cell)

Params are plain dict pytrees; per-layer weights are stacked on a leading
"layers" axis and consumed with lax.scan (keeps HLO size O(1) in depth,
enables deterministic arena layout of one contiguous buffer per leaf).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCell, SHAPE_CELLS
from repro.kernels.decode_attention import decode_attention_paged_kernel
from repro.launch.mesh import ShardCtx
from repro.models import ssm as ssm_mod
from repro.models.layers import (
    decode_attention_dense, decode_attention_seqpar, flash_attention,
    gelu_mlp, moe_capacity, moe_ffn, rms_norm, rope, swiglu)

Params = Dict[str, Any]


def _split_tree(rng, tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))


class Model:
    def __init__(self, cfg: ArchConfig, ctx: Optional[ShardCtx] = None):
        self.cfg = cfg
        self.ctx = ctx or ShardCtx(mesh=None)
        H, Hkv = cfg.num_heads, cfg.num_kv_heads
        # attention sharding mode (see DESIGN.md §4)
        self.q_shard = self.ctx.divides("heads", H) if H else False
        self.kv_shard = self.q_shard and self.ctx.divides("kv_heads", Hkv)
        self.dtype = jnp.dtype(cfg.param_dtype)

    # ------------------------------------------------------------------
    # parameter structure
    # ------------------------------------------------------------------
    def _attn_shapes(self):
        c = self.cfg
        return {
            "ln_attn": (c.d_model,),
            "wq": (c.d_model, c.num_heads * c.head_dim),
            "wk": (c.d_model, c.num_kv_heads * c.head_dim),
            "wv": (c.d_model, c.num_kv_heads * c.head_dim),
            "wo": (c.num_heads * c.head_dim, c.d_model),
        }

    def _attn_axes(self):
        fsdp = "fsdp" if self.cfg.zero_shard_params else None
        if self.q_shard:
            return {
                "ln_attn": (None,),
                "wq": (fsdp, "heads"),
                "wk": (fsdp, "kv_heads" if self.kv_shard else None),
                "wv": (fsdp, "kv_heads" if self.kv_shard else None),
                "wo": ("heads", fsdp),
            }
        return {"ln_attn": (None,), "wq": (fsdp, None), "wk": (fsdp, None),
                "wv": (fsdp, None), "wo": (fsdp, None)}

    def _mlp_shapes(self):
        c = self.cfg
        if c.family == "encoder":
            return {"ln_mlp": (c.d_model,), "w_up": (c.d_model, c.d_ff),
                    "b_up": (c.d_ff,), "w_down": (c.d_ff, c.d_model),
                    "b_down": (c.d_model,)}
        return {"ln_mlp": (c.d_model,), "w_gate": (c.d_model, c.d_ff),
                "w_up": (c.d_model, c.d_ff), "w_down": (c.d_ff, c.d_model)}

    def _mlp_axes(self):
        c = self.cfg
        fsdp = "fsdp" if c.zero_shard_params else None
        if c.family == "encoder":
            return {"ln_mlp": (None,), "w_up": (fsdp, "mlp"), "b_up": ("mlp",),
                    "w_down": ("mlp", fsdp), "b_down": (None,)}
        return {"ln_mlp": (None,), "w_gate": (fsdp, "mlp"),
                "w_up": (fsdp, "mlp"), "w_down": ("mlp", fsdp)}

    def _layer_shapes(self):
        c = self.cfg
        if c.family in ("dense", "vlm"):
            return {**self._attn_shapes(), **self._mlp_shapes()}
        if c.family == "encoder":
            return {**self._attn_shapes(), **self._mlp_shapes()}
        if c.family == "moe":
            d = {**self._attn_shapes(), "ln_mlp": (c.d_model,),
                 "router": (c.d_model, c.num_experts),
                 "we_gate": (c.num_experts, c.d_model, c.d_ff),
                 "we_up": (c.num_experts, c.d_model, c.d_ff),
                 "we_down": (c.num_experts, c.d_ff, c.d_model)}
            if c.moe_dense_residual:
                d.update({"wd_gate": (c.d_model, c.d_ff),
                          "wd_up": (c.d_model, c.d_ff),
                          "wd_down": (c.d_ff, c.d_model)})
            return d
        if c.family == "ssm":
            return {"ln": (c.d_model,), **ssm_mod.mamba1_param_shapes(c)}
        if c.family == "hybrid":
            return {"ln": (c.d_model,), **ssm_mod.mamba2_param_shapes(c)}
        raise ValueError(c.family)

    def _layer_axes(self):
        c = self.cfg
        fsdp = "fsdp" if c.zero_shard_params else None
        if c.family in ("dense", "vlm", "encoder"):
            return {**self._attn_axes(), **self._mlp_axes()}
        if c.family == "moe":
            d = {**self._attn_axes(), "ln_mlp": (None,),
                 "router": (fsdp, None),
                 "we_gate": ("experts", fsdp, None),
                 "we_up": ("experts", fsdp, None),
                 "we_down": ("experts", None, fsdp)}
            if c.moe_dense_residual:
                d.update({"wd_gate": (fsdp, "mlp"), "wd_up": (fsdp, "mlp"),
                          "wd_down": ("mlp", fsdp)})
            return d
        if c.family == "ssm":
            return {"ln": (None,), **ssm_mod.MAMBA1_PARAM_AXES}
        if c.family == "hybrid":
            return {"ln": (None,), **ssm_mod.MAMBA2_PARAM_AXES}
        raise ValueError(c.family)

    def _top_shapes(self):
        c = self.cfg
        d = {"final_norm": (c.d_model,)}
        if c.family != "encoder" or True:  # all families embed something
            d["embed"] = (c.padded_vocab, c.d_model)
        if not c.tie_embeddings:
            d["lm_head"] = (c.d_model, c.padded_vocab)
        if c.family == "hybrid":  # shared attention block (weights reused)
            d["shared"] = {**self._attn_shapes(), **self._mlp_shapes()}
        if c.frontend == "audio_stub":
            d["front_proj"] = (c.d_model, c.d_model)
        return d

    def _top_axes(self):
        c = self.cfg
        d = {"final_norm": (None,), "embed": ("vocab", None)}
        if not c.tie_embeddings:
            d["lm_head"] = (None, "vocab")
        if c.family == "hybrid":
            d["shared"] = {**self._attn_axes(), **self._mlp_axes()}
        if c.frontend == "audio_stub":
            d["front_proj"] = (None, None)
        return d

    def param_shapes(self):
        """Pytree of jax.ShapeDtypeStruct (no allocation)."""
        c = self.cfg
        L = c.num_layers
        layer = {k: (L,) + s for k, s in self._layer_shapes().items()}
        tree = {"layers": layer, **self._top_shapes()}
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s, self.dtype), tree,
            is_leaf=lambda x: isinstance(x, tuple))

    def param_logical_axes(self):
        layer = {k: ("layers",) + a for k, a in self._layer_axes().items()}
        return {"layers": layer, **self._top_axes()}

    def param_shardings(self):
        if self.ctx.mesh is None:
            return None
        shapes = self.param_shapes()
        axes = self.param_logical_axes()
        return jax.tree.map(
            lambda sd, ax: self.ctx.sharding(ax, sd.shape),
            shapes, axes, is_leaf=lambda x: isinstance(x, (tuple, jax.ShapeDtypeStruct)))

    def param_specs(self):
        """ShapeDtypeStructs with shardings attached (dry-run stand-ins)."""
        shapes = self.param_shapes()
        if self.ctx.mesh is None:
            return shapes
        shardings = self.param_shardings()
        return jax.tree.map(
            lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
            shapes, shardings,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct) or x is None)

    def init(self, rng) -> Params:
        shapes = self.param_shapes()
        keys = _split_tree(rng, shapes)

        def one(key, sd):
            if len(sd.shape) <= 1:
                # vectors default to 0; norms/A_log/D are fixed up below
                return jnp.zeros(sd.shape, sd.dtype)
            fan_in = sd.shape[-2] if len(sd.shape) >= 2 else sd.shape[-1]
            std = 0.02
            return (jax.random.normal(key, sd.shape, jnp.float32) * std).astype(sd.dtype)

        params = jax.tree.map(one, keys, shapes)
        # norm scales start at 1; mamba dt_bias/A_log get sane starts
        def fix(path, leaf):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name.startswith(("ln", "norm", "final_norm")):
                return jnp.ones_like(leaf)
            if name == "A_log":
                return jnp.zeros_like(leaf)  # A = -exp(0) = -1
            if name == "dt_bias":
                return jnp.full_like(leaf, math.log(math.e - 1))  # softplus->1.. mild
            if name == "D":
                return jnp.ones_like(leaf)
            return leaf
        params = jax.tree_util.tree_map_with_path(fix, params)
        if self.ctx.mesh is not None:
            params = jax.tree.map(jax.device_put, params, self.param_shardings())
        return params

    # ------------------------------------------------------------------
    # embedding / logits
    # ------------------------------------------------------------------
    def _embed(self, params, tokens):
        x = params["embed"][tokens]  # gather over vocab-sharded table
        return self.ctx.constrain(x, "batch", None, None)

    def _logits(self, params, x):
        head = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        x = rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = (x @ head).astype(jnp.float32)
        return self.ctx.constrain(logits, "batch", None, "vocab")

    def _inputs_to_x(self, params, batch):
        """Map a batch dict to embedded inputs [B, S, D] (frontend stubs)."""
        c = self.cfg
        if c.family == "encoder":
            x = batch["frames"].astype(self.dtype) @ params["front_proj"]
            return self.ctx.constrain(x, "batch", None, None)
        x = self._embed(params, batch["tokens"])
        if c.family == "vlm" and "vision_embeds" in batch:
            v = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([v, x], axis=1)
            x = self.ctx.constrain(x, "batch", None, None)
        return x

    # ------------------------------------------------------------------
    # attention block (full-sequence)
    # ------------------------------------------------------------------
    def _attn_full(self, x, lw, positions, with_cache: bool):
        c, ctx = self.cfg, self.ctx
        B, S, D = x.shape
        H, Hkv, Dh = c.num_heads, c.num_kv_heads, c.head_dim
        h = rms_norm(x, lw["ln_attn"], c.norm_eps)
        q = (h @ lw["wq"]).reshape(B, S, H, Dh)
        k = (h @ lw["wk"]).reshape(B, S, Hkv, Dh)
        v = (h @ lw["wv"]).reshape(B, S, Hkv, Dh)
        if self.q_shard:
            q = ctx.constrain(q, "batch", None, "heads", None)
        if self.kv_shard:
            k = ctx.constrain(k, "batch", None, "kv_heads", None)
            v = ctx.constrain(v, "batch", None, "kv_heads", None)
        q = rope(q, positions, c.rope_theta)
        k = rope(k, positions, c.rope_theta)
        attn = flash_attention(q, k, v, causal=c.causal, ctx=ctx)
        out = attn.reshape(B, S, H * Dh) @ lw["wo"]
        out = ctx.constrain(out, "batch", None, None)
        if with_cache:
            return out, (k, v)
        return out, None

    def _mlp(self, x, lw):
        c, ctx = self.cfg, self.ctx
        h = rms_norm(x, lw["ln_mlp"], c.norm_eps)
        if c.family == "encoder":
            return gelu_mlp(h, lw["w_up"], lw["b_up"], lw["w_down"],
                            lw["b_down"], ctx)
        return swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], ctx)

    def _moe(self, x, lw, lossless: bool):
        c, ctx = self.cfg, self.ctx
        B, S, D = x.shape
        h = rms_norm(x, lw["ln_mlp"], c.norm_eps)
        cap = moe_capacity(c, S, lossless=lossless)
        out, aux = moe_ffn(h, lw["router"], lw["we_gate"], lw["we_up"],
                           lw["we_down"], top_k=c.top_k, capacity=cap, ctx=ctx)
        if c.moe_dense_residual:
            out = out + swiglu(h, lw["wd_gate"], lw["wd_up"], lw["wd_down"], ctx)
        return out, aux

    # ------------------------------------------------------------------
    # full-sequence forward (training / prefill)
    # ------------------------------------------------------------------
    def forward(self, params, batch, *, collect_cache: bool = False,
                cache_len: Optional[int] = None):
        """Returns (logits [B, S, Vp], aux_loss, cache_or_None)."""
        c, ctx = self.cfg, self.ctx
        x = self._inputs_to_x(params, batch)
        B, S, D = x.shape
        positions = jnp.arange(S)[None, :]

        if c.family in ("dense", "vlm", "encoder", "moe"):
            def block(carry, lw):
                x, aux = carry
                attn_out, kv = self._attn_full(
                    x, lw, positions, with_cache=collect_cache)
                x = x + attn_out
                if c.family == "moe":
                    mlp_out, a = self._moe(x, lw, lossless=False)
                    aux = aux + a
                else:
                    mlp_out = self._mlp(x, lw)
                x = ctx.constrain(x + mlp_out, "batch", None, None)
                return (x, aux), kv

            body = jax.checkpoint(block) if c.remat else block
            (x, aux), kvs = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                         params["layers"])
            cache = None
            if collect_cache:
                k_all, v_all = kvs  # [L, B, S, Hkv, Dh]
                cache = self._pack_attn_cache(k_all, v_all, S, cache_len)
            return self._logits(params, x), aux / c.num_layers, cache

        if c.family == "ssm":
            def block(carry, lw):
                x = carry
                h = rms_norm(x, lw["ln"], c.norm_eps)
                y, st = ssm_mod.mamba1_prefill(h, lw, c, ctx)
                x = ctx.constrain(x + y, "batch", None, None)
                return x, st if collect_cache else None

            body = jax.checkpoint(block) if c.remat else block
            x, sts = jax.lax.scan(body, x, params["layers"])
            cache = None
            if collect_cache:
                h_all, buf_all = sts
                cache = {"ssm_h": h_all, "conv": buf_all,
                         "lengths": jnp.full((B,), S, jnp.int32)}
            return self._logits(params, x), jnp.zeros((), jnp.float32), cache

        if c.family == "hybrid":
            return self._hybrid_forward(params, x, positions, collect_cache,
                                        cache_len)
        raise ValueError(c.family)

    def _hybrid_forward(self, params, x, positions, collect_cache, cache_len):
        """Zamba2: scan over super-blocks = (period mamba2 layers + shared attn)."""
        c, ctx = self.cfg, self.ctx
        B, S, D = x.shape
        period = c.shared_attn_period
        n_super = c.num_layers // period
        shared = params["shared"]

        # reshape stacked layers [L, ...] -> [n_super, period, ...]
        sup_layers = jax.tree.map(
            lambda a: a.reshape((n_super, period) + a.shape[1:]),
            params["layers"])

        def mamba_block(carry, lw):
            x = carry
            h = rms_norm(x, lw["ln"], c.norm_eps)
            y, st = ssm_mod.mamba2_prefill(h, lw, c, ctx)
            x = ctx.constrain(x + y, "batch", None, None)
            return x, st if collect_cache else None

        mb = jax.checkpoint(mamba_block) if c.remat else mamba_block

        def super_block(carry, slw):
            x = carry
            x, sts = jax.lax.scan(mb, x, slw)
            attn_out, kv = self._attn_full(x, shared, positions,
                                           with_cache=collect_cache)
            x = x + attn_out
            x = x + self._mlp(x, shared)
            x = ctx.constrain(x, "batch", None, None)
            return x, (sts, kv)

        x, (sts, kvs) = jax.lax.scan(super_block, x, sup_layers)
        cache = None
        if collect_cache:
            S_all, bufs = sts  # [n_super, period, ...]
            flat = lambda a: a.reshape((n_super * period,) + a.shape[2:])
            k_all, v_all = kvs  # [n_super, B, S, Hkv, Dh]
            attn_cache = self._pack_attn_cache(k_all, v_all, S, cache_len,
                                               n_layers=n_super)
            cache = {"ssm_h": flat(S_all),
                     "conv": jax.tree.map(flat, bufs),
                     **attn_cache}
        return self._logits(params, x), jnp.zeros((), jnp.float32), cache

    def _pack_attn_cache(self, k_all, v_all, S, cache_len, n_layers=None):
        """Pad prefill K/V [L,B,S,Hkv,Dh] to cache capacity, reorder to the
        cache layout, apply cache shardings."""
        cap = cache_len or S
        pad = cap - S
        if pad:
            pz = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
            k_all = jnp.pad(k_all, pz)
            v_all = jnp.pad(v_all, pz)
        if self.cache_layout == "bhsd":
            k_all = k_all.transpose(0, 1, 3, 2, 4)
            v_all = v_all.transpose(0, 1, 3, 2, 4)
        B = k_all.shape[1]
        axes = self.cache_logical_axes()
        k_all = self.ctx.constrain(k_all, *axes)
        v_all = self.ctx.constrain(v_all, *axes)
        return {"k": k_all, "v": v_all,
                "lengths": jnp.full((B,), S, jnp.int32)}

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    @property
    def cache_layout(self) -> str:
        """"bshd" [L,B,S,Hkv,Dh] (baseline) or head-major "bhsd"
        [L,B,Hkv,S,Dh] (transpose-free decode dots; FLAGS.kv_cache_head_major)."""
        from repro.models.tuning import FLAGS
        return "bhsd" if FLAGS.kv_cache_head_major else "bshd"

    def cache_logical_axes(self):
        if self.cache_layout == "bhsd":  # [L, B, Hkv, S, Dh]
            if self.kv_shard:
                return ("layers", "batch", "kv_heads", None, None)
            return ("layers", "batch", None, "kv_seq", None)
        # [L, B, S, Hkv, Dh]
        if self.kv_shard:
            return ("layers", "batch", None, "kv_heads", None)
        return ("layers", "batch", "kv_seq", None, None)

    def init_cache(self, batch_size: int, max_seq: int):
        """Zero-initialized cache pytree (engine path; dry-run uses specs)."""
        specs = self.cache_specs(batch_size, max_seq)
        def mk(sd):
            if sd.sharding is not None:
                return jax.device_put(jnp.zeros(sd.shape, sd.dtype), sd.sharding)
            return jnp.zeros(sd.shape, sd.dtype)
        return jax.tree.map(mk, specs)

    def cache_specs(self, B: int, S: int):
        """ShapeDtypeStructs (with shardings) for the decode cache."""
        c, ctx = self.cfg, self.ctx
        L, Hkv, Dh = c.num_layers, c.num_kv_heads, c.head_dim
        out = {}
        def sds(shape, axes, dtype=None):
            sh = ctx.sharding(axes, shape) if ctx.mesh is not None else None
            return jax.ShapeDtypeStruct(shape, dtype or self.dtype, sharding=sh)

        if c.family in ("dense", "vlm", "moe", "hybrid"):
            n_l = (c.num_layers // c.shared_attn_period
                   if c.family == "hybrid" else L)
            shape = ((n_l, B, Hkv, S, Dh) if self.cache_layout == "bhsd"
                     else (n_l, B, S, Hkv, Dh))
            axes = self.cache_logical_axes()
            out["k"] = sds(shape, axes)
            out["v"] = sds(shape, axes)
        if c.family == "ssm":
            di, N, K = c.d_inner, c.ssm_state, c.ssm_conv
            out["ssm_h"] = sds((L, B, di, N), ("layers", "batch", "ssm_inner", None),
                               jnp.float32)
            out["conv"] = sds((L, B, K - 1, di),
                              ("layers", "batch", None, "ssm_inner"))
        if c.family == "hybrid":
            di, N, K, H, P_ = (c.d_inner, c.ssm_state, c.ssm_conv,
                               c.ssm_nheads, c.ssm_head_dim)
            out["ssm_h"] = sds((L, B, H, P_, N),
                               ("layers", "batch", "ssm_heads", None, None),
                               jnp.float32)
            out["conv"] = (
                sds((L, B, K - 1, di), ("layers", "batch", None, "ssm_inner")),
                sds((L, B, K - 1, N), ("layers", "batch", None, None)),
                sds((L, B, K - 1, N), ("layers", "batch", None, None)))
        out["lengths"] = sds((B,), ("batch",), jnp.int32)
        return out

    # ------------------------------------------------------------------
    # paged decode (block-table KV; serving/blockpool.py)
    # ------------------------------------------------------------------
    def paged_cache_logical_axes(self):
        """Axes for the paged K/V pools [L, NB, bs, Hkv, Dh]. The pools
        carry no batch dim (blocks are shared across requests), so only the
        kv-head axis can shard; seqpar layouts stay on the slot pool."""
        if self.kv_shard:
            return ("layers", None, None, "kv_heads", None)
        return ("layers", None, None, None, None)

    def paged_cache_specs(self, B: int, S: int, n_blocks: int,
                          block_size: int):
        """ShapeDtypeStructs for the paged decode cache. Only block_tables
        and lengths are bucket-sized ([B, ...]); the K/V pools are identical
        across buckets, so every bucket's captured program closes over the
        same pool shapes and templates group exactly as before."""
        c, ctx = self.cfg, self.ctx
        if c.family not in ("dense", "vlm", "moe"):
            raise ValueError(f"{c.family} has no paged decode cache")
        L, Hkv, Dh = c.num_layers, c.num_kv_heads, c.head_dim
        MB = -(-S // block_size)

        def sds(shape, axes, dtype=None):
            sh = ctx.sharding(axes, shape) if ctx.mesh is not None else None
            return jax.ShapeDtypeStruct(shape, dtype or self.dtype, sharding=sh)

        axes = self.paged_cache_logical_axes()
        return {"block_tables": sds((B, MB), ("batch", None), jnp.int32),
                "k": sds((L, n_blocks, block_size, Hkv, Dh), axes),
                "lengths": sds((B,), ("batch",), jnp.int32),
                "v": sds((L, n_blocks, block_size, Hkv, Dh), axes)}

    def init_cache_paged(self, B: int, S: int, n_blocks: int,
                         block_size: int):
        """Zero-initialized paged cache pytree with valid dense block
        tables: row b owns consecutive physical blocks (scratch block 0
        backs any overflow). Benchmark/test-harness path — the serving
        engine builds its pool through ``PagedKVCachePool`` instead."""
        import numpy as np
        specs = self.paged_cache_specs(B, S, n_blocks, block_size)

        def mk(sd):
            z = jnp.zeros(sd.shape, sd.dtype)
            return jax.device_put(z, sd.sharding) if sd.sharding is not None \
                else z
        cache = jax.tree.map(mk, specs)
        MB = -(-S // block_size)
        bt = np.zeros((B, MB), np.int32)
        nb = 1
        for b in range(B):
            for j in range(MB):
                if nb < n_blocks:
                    bt[b, j] = nb
                    nb += 1
        tables = jnp.asarray(bt)
        sh = specs["block_tables"].sharding
        if sh is not None:
            tables = jax.device_put(tables, sh)
        return {**cache, "block_tables": tables}

    def _attn_decode_paged(self, x_t, lw, k_pool, v_pool, block_tables,
                           lengths):
        """One-token attention against a per-layer paged pool. The new K/V
        scatters into each row's current write slot (block_tables[row,
        length//bs], offset length%bs); attention reads each row's live
        table blocks, up to ``lengths[row]``, through the paged kernel.
        Padded rows point every table entry at the scratch block; their
        output is never read."""
        c = self.cfg
        B, D = x_t.shape
        H, Hkv, Dh = c.num_heads, c.num_kv_heads, c.head_dim
        NB, bs = k_pool.shape[0], k_pool.shape[1]  # per-layer [NB,bs,Hkv,Dh]
        MB = block_tables.shape[1]
        h = rms_norm(x_t, lw["ln_attn"], c.norm_eps)
        q = (h @ lw["wq"]).reshape(B, 1, H, Dh)
        k = (h @ lw["wk"]).reshape(B, 1, Hkv, Dh)
        v = (h @ lw["wv"]).reshape(B, 1, Hkv, Dh)
        pos = lengths[:, None]
        q = rope(q, pos, c.rope_theta)
        k = rope(k, pos, c.rope_theta)
        # scatter new K/V: flatten blocks to [NB*bs, Hkv, Dh] positions.
        # Inactive rows all target scratch slot 0 — duplicate writes race
        # but the result is never read unmasked.
        wblk = block_tables[jnp.arange(B), jnp.clip(lengths // bs, 0, MB - 1)]
        widx = wblk * bs + lengths % bs
        kf = k_pool.reshape((NB * bs,) + k_pool.shape[2:])
        vf = v_pool.reshape((NB * bs,) + v_pool.shape[2:])
        kf = kf.at[widx].set(k[:, 0].astype(kf.dtype))
        vf = vf.at[widx].set(v[:, 0].astype(vf.dtype))
        k_pool = kf.reshape(k_pool.shape)
        v_pool = vf.reshape(v_pool.shape)
        out = self._paged_attention(q[:, 0], k_pool, v_pool, block_tables,
                                    lengths)
        out = out.reshape(B, H * Dh) @ lw["wo"]
        return self.ctx.constrain(out, "batch", None), k_pool, v_pool

    def _paged_attention(self, q, k_pool, v_pool, block_tables, lengths):
        """q: [B, H, Dh] -> [B, H, Dh] over the live blocks of each row.
        On a mesh the kernel runs per shard: rows split as the batch axis
        does, kv heads (with their query groups) as the pool's head axis
        does; every shard holds the whole table."""
        ctx = self.ctx
        if ctx.mesh is None:
            return decode_attention_paged_kernel(q, k_pool, v_pool,
                                                 block_tables, lengths)
        rows = ctx.spec(("batch",), q.shape[:1])[0]
        heads = ctx.spec(self.paged_cache_logical_axes()[1:],
                         k_pool.shape)[2]
        mapped = jax.shard_map(
            decode_attention_paged_kernel, mesh=ctx.mesh,
            in_specs=(P(rows, heads, None), P(None, None, heads, None),
                      P(None, None, heads, None), P(rows, None), P(rows)),
            out_specs=P(rows, heads, None), check_vma=False)
        return mapped(q, k_pool, v_pool, block_tables, lengths)

    def decode_step_paged(self, params, cache, tokens):
        """Paged-layout decode step: same contract as ``decode_step`` but
        the cache pytree is {block_tables, k, lengths, v} with block-major
        pools. tokens: [B] int32 -> (cache', logits [B, Vp])."""
        c, ctx = self.cfg, self.ctx
        if c.family not in ("dense", "vlm", "moe"):
            raise ValueError(f"{c.family} has no paged decode step")
        lengths = cache["lengths"]
        bt = cache["block_tables"]
        x = self._embed(params, tokens[:, None])[:, 0]  # [B, D]

        def block(carry, xs):
            x = carry
            lw, kc, vc = xs
            a, kc, vc = self._attn_decode_paged(x, lw, kc, vc, bt, lengths)
            x = x + a
            if c.family == "moe":
                mo, _ = self._moe(x[:, None, :], lw, lossless=True)
                x = x + mo[:, 0, :]
            else:
                x = x + self._mlp(x, lw)
            return ctx.constrain(x, "batch", None), (kc, vc)

        x, (k_new, v_new) = jax.lax.scan(
            block, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {**cache, "k": k_new, "v": v_new, "lengths": lengths + 1}
        logits = self._logits(params, x[:, None, :])[:, 0]
        return new_cache, logits

    def _attn_decode(self, x_t, lw, k_cache, v_cache, lengths):
        """One-token attention vs per-layer cache. x_t: [B, D].
        Returns (out [B, D], k_cache', v_cache')."""
        c, ctx = self.cfg, self.ctx
        B, D = x_t.shape
        H, Hkv, Dh = c.num_heads, c.num_kv_heads, c.head_dim
        h = rms_norm(x_t, lw["ln_attn"], c.norm_eps)
        q = (h @ lw["wq"]).reshape(B, 1, H, Dh)
        k = (h @ lw["wk"]).reshape(B, 1, Hkv, Dh)
        v = (h @ lw["wv"]).reshape(B, 1, Hkv, Dh)
        pos = lengths[:, None]  # new token position
        q = rope(q, pos, c.rope_theta)
        k = rope(k, pos, c.rope_theta)
        layout = self.cache_layout
        if self.kv_shard or ctx.mesh is None or not self._seqpar_axes():
            # write then attend (head-sharded or replicated cache)
            waxis = 1 if layout == "bhsd" else 0

            def write(cache, new, l):
                # new: [1, Hkv, Dh] -> bhsd update [Hkv, 1, Dh]
                upd = new.transpose(1, 0, 2) if layout == "bhsd" else new
                return jax.lax.dynamic_update_slice_in_dim(
                    cache, upd.astype(cache.dtype), l, axis=waxis)
            k_cache = jax.vmap(write)(k_cache, k, lengths)
            v_cache = jax.vmap(write)(v_cache, v, lengths)
            out = decode_attention_dense(q, k_cache, v_cache, lengths,
                                         layout=layout)
        else:
            out, k_cache, v_cache = decode_attention_seqpar(
                q, k_cache, v_cache, k[:, 0], v[:, 0], lengths,
                mesh=ctx.mesh, batch_axes=self._batch_axes(k_cache.shape[0]),
                seq_axes=self._seqpar_axes(), layout=layout)
        out = out.reshape(B, H * Dh) @ lw["wo"]
        return ctx.constrain(out, "batch", None), k_cache, v_cache

    def _batch_axes(self, B):
        axes = list(self.ctx.data_axes)
        import math as _m
        while axes and B % _m.prod(self.ctx.mesh.shape[a] for a in axes):
            axes.pop(0)
        return tuple(axes)

    def _seqpar_axes(self):
        """Mesh axes carrying the KV sequence dim in seqpar mode."""
        if self.ctx.mesh is None or self.kv_shard:
            return ()
        spec = self.ctx._resolve_dim("kv_seq", 1 << 30)  # divisibility-free probe
        if spec is None:
            return ()
        return (spec,) if isinstance(spec, str) else tuple(spec)

    def decode_step(self, params, cache, tokens):
        """tokens: [B] int32. Returns (cache', logits [B, Vp])."""
        c, ctx = self.cfg, self.ctx
        lengths = cache["lengths"]
        B = tokens.shape[0]
        x = self._embed(params, tokens[:, None])[:, 0]  # [B, D]

        if c.family in ("dense", "vlm", "moe"):
            def block(carry, xs):
                x = carry
                lw, kc, vc = xs
                a, kc, vc = self._attn_decode(x, lw, kc, vc, lengths)
                x = x + a
                if c.family == "moe":
                    mo, _ = self._moe(x[:, None, :], lw, lossless=True)
                    x = x + mo[:, 0, :]
                else:
                    x = x + self._mlp(x, lw)
                return ctx.constrain(x, "batch", None), (kc, vc)

            x, (k_new, v_new) = jax.lax.scan(
                block, x, (params["layers"], cache["k"], cache["v"]))
            new_cache = {**cache, "k": k_new, "v": v_new,
                         "lengths": lengths + 1}
        elif c.family == "ssm":
            def block(carry, xs):
                x = carry
                lw, h_l, buf_l = xs
                hN = rms_norm(x, lw["ln"], c.norm_eps)
                y, (h_l, buf_l) = ssm_mod.mamba1_decode(hN, (h_l, buf_l), lw, c, ctx)
                return ctx.constrain(x + y, "batch", None), (h_l, buf_l)

            x, (h_new, buf_new) = jax.lax.scan(
                block, x, (params["layers"], cache["ssm_h"], cache["conv"]))
            new_cache = {**cache, "ssm_h": h_new, "conv": buf_new,
                         "lengths": lengths + 1}
        elif c.family == "hybrid":
            x, new_cache = self._hybrid_decode(params, cache, x, lengths)
        else:
            raise ValueError(f"{c.family} has no decode step")

        logits = self._logits(params, x[:, None, :])[:, 0]
        return new_cache, logits

    def _hybrid_decode(self, params, cache, x, lengths):
        c, ctx = self.cfg, self.ctx
        period = c.shared_attn_period
        n_super = c.num_layers // period
        shared = params["shared"]
        resh = lambda a: a.reshape((n_super, period) + a.shape[1:])
        sup_layers = jax.tree.map(resh, params["layers"])
        sup_h = resh(cache["ssm_h"])
        sup_conv = jax.tree.map(resh, cache["conv"])

        def mamba_block(carry, xs):
            x = carry
            lw, h_l, bufs = xs
            hN = rms_norm(x, lw["ln"], c.norm_eps)
            y, (h_l, bufs) = ssm_mod.mamba2_decode(hN, (h_l, bufs), lw, c, ctx)
            return ctx.constrain(x + y, "batch", None), (h_l, bufs)

        def super_block(carry, xs):
            x = carry
            slw, h_s, conv_s, kc, vc = xs
            x, (h_s, conv_s) = jax.lax.scan(mamba_block, x, (slw, h_s, conv_s))
            a, kc, vc = self._attn_decode(x, shared, kc, vc, lengths)
            x = x + a
            x = x + self._mlp(x, shared)
            return ctx.constrain(x, "batch", None), (h_s, conv_s, kc, vc)

        x, (h_new, conv_new, k_new, v_new) = jax.lax.scan(
            super_block, x, (sup_layers, sup_h, sup_conv, cache["k"], cache["v"]))
        flat = lambda a: a.reshape((c.num_layers,) + a.shape[2:])
        new_cache = {**cache,
                     "ssm_h": flat(h_new),
                     "conv": jax.tree.map(flat, conv_new),
                     "k": k_new, "v": v_new,
                     "lengths": lengths + 1}
        return x, new_cache

    # ------------------------------------------------------------------
    # prefill wrapper + loss + input specs
    # ------------------------------------------------------------------
    def prefill(self, params, batch, cache_len: Optional[int] = None):
        logits, _, cache = self.forward(params, batch, collect_cache=True,
                                        cache_len=cache_len)
        return logits[:, -1], cache

    def loss_fn(self, params, batch):
        c = self.cfg
        logits, aux, _ = self.forward(params, batch)
        labels = batch["labels"]
        if c.family == "vlm":  # logits cover vision prefix + text
            logits = logits[:, -labels.shape[1]:]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        mask = (batch.get("loss_mask") if "loss_mask" in batch
                else jnp.ones_like(labels, jnp.float32))
        nll = ((lse - ll) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        return nll + 0.01 * aux, {"nll": nll, "aux": aux}

    def input_specs(self, shape_name: str):
        """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
        c, ctx = self.cfg, self.ctx
        cell = SHAPE_CELLS[shape_name]
        B, S = cell.global_batch, cell.seq_len

        def sds(shape, axes, dtype=jnp.int32):
            sh = ctx.sharding(axes, shape) if ctx.mesh is not None else None
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

        if cell.kind in ("train", "prefill"):
            if c.family == "encoder":
                batch = {"frames": sds((B, S, c.d_model), ("batch", None, None),
                                       self.dtype)}
            elif c.family == "vlm":
                sv = c.frontend_seq
                batch = {"tokens": sds((B, S - sv), ("batch", None)),
                         "vision_embeds": sds((B, sv, c.d_model),
                                              ("batch", None, None), self.dtype)}
            else:
                batch = {"tokens": sds((B, S), ("batch", None))}
            if cell.kind == "train":
                lab_s = S - c.frontend_seq if c.family == "vlm" else S
                batch["labels"] = sds((B, lab_s), ("batch", None))
            return batch
        # decode: cache + one token
        return {"cache": self.cache_specs(B, S),
                "tokens": sds((B,), ("batch",))}
