"""GQA attention: dense, chunked (flash-style jnp), and decode-with-cache paths.

Why a chunked jnp path exists: at 32k+ sequence a dense (S, T) score tensor
cannot be materialized on any real device, and the dry-run's memory analysis
must prove the step *fits*. The chunked path is the TPU-native flash-attention
structure (online softmax over KV blocks) expressed with lax loops so XLA never
materializes more than (q_chunk, kv_chunk) scores; the Pallas kernel in
`repro.kernels.flash_attention` implements the same blocking in VMEM for the
real TPU target, and this path doubles as its distributed wrapper/reference.

Supports: GQA (Hq = G * Hkv), RoPE, qk-RMSNorm (qwen3), sliding-window (danube),
KV-cache prefill/decode with ring-buffer caches for SWA.

Named scopes, stable because the chip benchmark's device-time metrics read
them from the compiled programs' metadata: `attn`, holding `qkv` (projections,
qk-norm and RoPE), `kv_write` (the cache build in prefill, the one-position
update in decode), `core` (scores, mask, softmax and the product with V) and
`out` (the `wo` projection).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, rms_head_norm
from repro.models.params import PD
from repro.parallel.axes import shard

NEG_INF = -1e30


# ------------------------------------------------------------------ param defs
def attn_defs(cfg: ModelConfig, d_in: int | None = None) -> dict:
    d = d_in if d_in is not None else cfg.d_model
    s = 0.02
    defs = {
        "wq": PD((d, cfg.num_heads * cfg.head_dim), (None, "tp"), stddev=s),
        "wk": PD((d, cfg.num_kv_heads * cfg.head_dim), (None, "tp"), stddev=s),
        "wv": PD((d, cfg.num_kv_heads * cfg.head_dim), (None, "tp"), stddev=s),
        "wo": PD((cfg.num_heads * cfg.head_dim, d), ("tp", None), stddev=s),
    }
    if cfg.qk_norm:
        defs["q_norm"] = PD((cfg.head_dim,), (None,), init="ones", dtype=jnp.float32)
        defs["k_norm"] = PD((cfg.head_dim,), (None,), init="ones", dtype=jnp.float32)
    return defs


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """x: (B, S, D) -> q (B,S,Hkv,G,dh), k/v (B,S,Hkv,dh), RoPE'd + qk-normed."""
    with jax.named_scope("qkv"):
        B, S, _ = x.shape
        Hq, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        G = Hq // Hkv
        q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, Hq, dh)
        k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, Hkv, dh)
        v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, Hkv, dh)
        if cfg.qk_norm:
            q = rms_head_norm(p["q_norm"], q)
            k = rms_head_norm(p["k_norm"], k)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        q = q.reshape(B, S, Hkv, G, dh)
        # Heads shard over tp; seq stays unsharded here (Megatron SP applies only to
        # the norm/residual regions — sharding seq over the same mesh axis as heads
        # would be an illegal double use of the axis).
        q = shard(q, "dp", None, "tp", None, None)
        k = shard(k, "dp", None, "tp", None)
        v = shard(v, "dp", None, "tp", None)
        return q, k, v


# ------------------------------------------------------------- dense attention
def _dense_attention(q, k, v, q_pos, k_pos, window):
    """Reference O(S*T) attention. q: (B,S,Hkv,G,dh); k/v: (B,T,Hkv,dh)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    s = s * scale
    mask = k_pos[None, :] <= q_pos[:, None]  # causal
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return out


# ----------------------------------------------------- chunked flash attention
def _chunked_attention(q, k, v, window, chunk, differentiable: bool = False):
    """Causal flash-style attention with online softmax over KV chunks.

    Never materializes more than (chunk, chunk) scores per (B, Hkv, G).
    q: (B, S, Hkv, G, dh); k, v: (B, S, Hkv, dh). Self-attention (q_pos == k_pos).

    `differentiable=True` (training): the inner KV loop is a static-bound scan
    over all chunks with masking — reverse-mode AD cannot differentiate a
    dynamic-bound fori_loop. Costs ~2x the causal-skipped flops on the score
    einsums; the Pallas kernel recovers the skip on real hardware. Inference
    paths keep the dynamic lower/upper bounds (causal + window skipping).
    """
    B, S, Hkv, G, dh = q.shape
    assert S % chunk == 0, (S, chunk)
    nq = S // chunk
    scale = dh**-0.5
    w_chunks = None if window is None else (window + chunk - 1) // chunk + 1

    qr = q.reshape(B, nq, chunk, Hkv, G, dh)

    def q_step(_, qi):
        qc = jax.lax.dynamic_index_in_dim(qr, qi, axis=1, keepdims=False)
        q_pos = qi * chunk + jnp.arange(chunk)

        def kv_block(carry, ki):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, ki * chunk, chunk, axis=1)
            vc = jax.lax.dynamic_slice_in_dim(v, ki * chunk, chunk, axis=1)
            k_pos = ki * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc, preferred_element_type=jnp.float32)
            s = s * scale
            mask = k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m2 = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc)
            acc2 = acc * corr[..., None].astype(acc.dtype) + pv
            return (m2, l2, acc2), None

        init = (
            jnp.full((B, Hkv, G, chunk), NEG_INF, jnp.float32),
            jnp.zeros((B, Hkv, G, chunk), jnp.float32),
            jnp.zeros((B, Hkv, G, chunk, dh), v.dtype),
        )
        if differentiable:
            (m, l, acc), _ = jax.lax.scan(kv_block, init, jnp.arange(nq))
        else:
            body = lambda ki, c: kv_block(c, ki)[0]  # noqa: E731
            lo = 0 if w_chunks is None else jnp.maximum(0, qi + 1 - w_chunks)
            m, l, acc = jax.lax.fori_loop(lo, qi + 1, body, init)
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        # (B, Hkv, G, chunk, dh) -> (B, chunk, Hkv, G, dh)
        return None, jnp.moveaxis(out, 3, 1)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, B, chunk, Hkv, G, dh) -> (B, S, Hkv, G, dh)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, Hkv, G, dh)
    return out


def self_attention(cfg: ModelConfig, p: dict, x: jax.Array, positions=None) -> jax.Array:
    """Full-sequence causal attention (training: differentiable paths only)."""
    with jax.named_scope("attn"):
        B, S, _ = x.shape
        pos = jnp.arange(S) if positions is None else positions
        q, k, v = _project_qkv(cfg, p, x, pos)
        with jax.named_scope("core"):
            if cfg.attn_impl == "dense" or S <= cfg.attn_chunk:
                out = _dense_attention(q, k, v, pos, pos, cfg.sliding_window)
            elif cfg.attn_impl == "pallas":
                from repro.kernels.flash_attention import ops as fa_ops

                out = fa_ops.flash_attention(q, k, v, window=cfg.sliding_window)
            else:
                # flash-attention custom VJP: O(S*d) residuals, scores recomputed in
                # bwd — a scan-based differentiable path would store every (c, c)
                # fp32 score block and blow HBM at 4k+ sequal lengths
                from repro.models.flash_vjp import flash_attention_vjp

                out = flash_attention_vjp(q, k, v, cfg.sliding_window, cfg.attn_chunk)
        with jax.named_scope("out"):
            out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
            out = out @ p["wo"].astype(out.dtype)
            return shard(out, "dp", "sp", None)


# ----------------------------------------------------------------- KV caching
def cache_len(cfg: ModelConfig, max_len: int) -> int:
    if cfg.sliding_window is not None:
        return min(cfg.sliding_window, max_len)
    return max_len


def cache_axes(cfg: ModelConfig, cp: bool = False) -> tuple:
    """Logical axes for a (B, W, Hkv, dh) KV cache under the current mesh.

    KV heads shard over tp when they divide evenly; otherwise the tp axes move
    to the cache-length dim (sequence-sharded decode attention — GSPMD turns
    the softmax into the flash-decode partial max/sum all-reduce). Without the
    fallback, a kv=8 cache on a 16-way model axis would be *replicated* 16x,
    which is what made several decode cells burst past HBM in the first sweep.
    """
    from repro.parallel.axes import axes_size

    tp = axes_size("tp")
    heads_shardable = tp > 1 and cfg.num_kv_heads % tp == 0
    if heads_shardable:
        return ("dp", "cp" if cp else None, "tp", None)
    seq = ("cp", "tp") if cp else "tp"
    return ("dp", seq, None, None)


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, cp: bool = False) -> dict:
    """Zeroed KV cache, sharded per cache_axes."""
    W = cache_len(cfg, max_len)
    shp = (batch, W, cfg.num_kv_heads, cfg.head_dim)
    ax = cache_axes(cfg, cp)
    k = shard(jnp.zeros(shp, cfg.compute_dtype), *ax)
    v = shard(jnp.zeros(shp, cfg.compute_dtype), *ax)
    return {"k": k, "v": v}


def attn_cache_specs(cfg: ModelConfig, cp: bool = False):
    ax = cache_axes(cfg, cp)
    return {"k": ax, "v": ax}


def prefill_attention(cfg: ModelConfig, p: dict, x: jax.Array, max_len: int, cp: bool = False):
    """Full-seq attention that also returns a decode-ready KV cache.

    Token t lands in cache slot t (full) or t % W (ring buffer, SWA).
    """
    with jax.named_scope("attn"):
        B, S, _ = x.shape
        pos = jnp.arange(S)
        q, k, v = _project_qkv(cfg, p, x, pos)
        with jax.named_scope("core"):
            if cfg.attn_impl == "dense" or S <= cfg.attn_chunk:
                out = _dense_attention(q, k, v, pos, pos, cfg.sliding_window)
            else:
                out = _chunked_attention(q, k, v, cfg.sliding_window, cfg.attn_chunk)
        with jax.named_scope("out"):
            out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
            out = shard(out @ p["wo"].astype(out.dtype), "dp", None, None)

        with jax.named_scope("kv_write"):
            W = cache_len(cfg, max_len)
            cache = init_attn_cache(cfg, B, max_len, cp=cp)
            if cfg.sliding_window is not None and S > W:
                # keep last W tokens, permuted into ring order (slot = t mod W)
                tail_t = jnp.arange(S - W, S)
                ck = jnp.take(k, tail_t, axis=1)
                cv = jnp.take(v, tail_t, axis=1)
                slots = jnp.argsort(tail_t % W)
                cache = {"k": jnp.take(ck, slots, axis=1), "v": jnp.take(cv, slots, axis=1)}
            else:
                cache = {
                    "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1),
                    "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1),
                }
            ax = cache_axes(cfg, cp)
            cache = {kk: shard(vv, *ax) for kk, vv in cache.items()}
        return out, cache


def decode_attention(cfg: ModelConfig, p: dict, x: jax.Array, cache: dict, pos: jax.Array, cp: bool = False):
    """One-token decode: q over the KV cache (the paper's skinny-GEMM regime).

    x: (B, 1, D); pos: scalar int32 = index of the current token (0-based).
    Returns (out (B,1,D), updated cache).
    """
    with jax.named_scope("attn"):
        B, _, _ = x.shape
        Hq, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        G = Hq // Hkv
        positions = jnp.full((B, 1), pos, jnp.int32)
        q, k, v = _project_qkv(cfg, p, x, positions)

        K, V = cache["k"], cache["v"]
        W = K.shape[1]
        with jax.named_scope("kv_write"):
            write = pos % W if cfg.sliding_window is not None else pos
            K = jax.lax.dynamic_update_slice(K, k, (0, write, 0, 0))
            V = jax.lax.dynamic_update_slice(V, v, (0, write, 0, 0))
            ax = cache_axes(cfg, cp)
            K = shard(K, *ax)
            V = shard(V, *ax)

        with jax.named_scope("core"):
            slot = jnp.arange(W)
            if cfg.sliding_window is not None:
                # slot i holds token t = pos - ((pos - i) mod W); valid iff t >= 0
                t = pos - jnp.mod(pos - slot, W)
                valid = t >= 0
            else:
                valid = slot <= pos

            scale = dh**-0.5
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q, K, preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
            # softmax over a (possibly context-parallel-sharded) axis: GSPMD inserts the
            # flash-decode-style partial max/sum all-reduces automatically.
            pr = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bhgqk,bkhd->bqhgd", pr.astype(V.dtype), V)
        with jax.named_scope("out"):
            out = out.reshape(B, 1, Hq * dh) @ p["wo"].astype(x.dtype)
            out = shard(out, "dp", None, None)
        return out, {"k": K, "v": V}
