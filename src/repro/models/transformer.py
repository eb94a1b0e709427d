"""Model assembly: scan-over-layers transformer supporting all assigned families.

Families:
  dense / audio / vlm : [norm -> GQA attn -> norm -> MLP] x L
  moe                 : MLP replaced by routed MoE (+ shared experts / dense
                        residual); optional leading dense layers (deepseek)
  ssm (rwkv6)         : [ln -> time-mix -> ln -> channel-mix] x L
  hybrid (zamba2)     : Mamba2 backbone with a *shared* attn+MLP block applied
                        every `attn_every` layers (python-loop assembly, so the
                        shared block's KV caches exist only where it is applied)

Execution modes: train/forward (no cache), prefill (returns decode cache),
decode (one token, O(1) state/KV updates).

Named scopes mark each part of the step in the compiled programs' metadata,
where the chip benchmark reads device time by part; later changes keep the
names: `embed`, `norm`, `attn` (with its children, see `attention.py`), `mlp`
and `head`.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba2, moe, rwkv6
from repro.models.layers import (
    apply_norm,
    embed_defs,
    embed_lookup,
    head_defs,
    lm_logits,
    norm_defs,
)
from repro.models.mlp import apply_mlp, mlp_defs
from repro.models.params import PD, init_params, param_specs, param_shapes, stacked
from repro.parallel.axes import shard

AUX_KEYS = ("moe_lb_loss", "moe_z_loss", "moe_drop_frac")


def zero_aux() -> dict:
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


# ------------------------------------------------------------------ layer defs
def _dense_layer_defs(cfg: ModelConfig, moe_layer: bool) -> dict:
    d = {
        "ln1": norm_defs(cfg.d_model, cfg.norm),
        "attn": attn.attn_defs(cfg),
        "ln2": norm_defs(cfg.d_model, cfg.norm),
    }
    if moe_layer:
        d["moe"] = moe.moe_defs(cfg)
    else:
        ff = cfg.moe.dense_d_ff if (cfg.moe and cfg.moe.dense_d_ff) else cfg.d_ff
        d["mlp"] = mlp_defs(cfg.d_model, ff, cfg.gated_mlp)
    return d


def _rwkv_layer_defs(cfg: ModelConfig) -> dict:
    r = rwkv6.rwkv6_defs(cfg)
    return {
        "ln1": norm_defs(cfg.d_model, "layernorm"),
        "tm": r["tm"],
        "ln2": norm_defs(cfg.d_model, "layernorm"),
        "cm": r["cm"],
    }


def _mamba_layer_defs(cfg: ModelConfig) -> dict:
    return {"ln1": norm_defs(cfg.d_model, cfg.norm), "mamba": mamba2.mamba2_defs(cfg)}


def _shared_block_defs(cfg: ModelConfig) -> dict:
    return {
        "ln1": norm_defs(cfg.d_model, cfg.norm),
        "attn": attn.attn_defs(cfg),
        "ln2": norm_defs(cfg.d_model, cfg.norm),
        "mlp": mlp_defs(cfg.d_model, cfg.d_ff, cfg.gated_mlp),
    }


# ----------------------------------------------------------------- layer apply
def _apply_dense_layer(cfg, p, x, mode, cache=None, pos=None, max_len=0, cp=False):
    h = apply_norm(p["ln1"], x)
    new_cache: dict[str, Any] = {}
    if mode == "train":
        a = attn.self_attention(cfg, p["attn"], h)
    elif mode == "prefill":
        a, kv = attn.prefill_attention(cfg, p["attn"], h, max_len, cp=cp)
        new_cache["kv"] = kv
    else:  # decode
        a, kv = attn.decode_attention(cfg, p["attn"], h, cache["kv"], pos, cp=cp)
        new_cache["kv"] = kv
    x = x + a
    h = apply_norm(p["ln2"], x)
    if "moe" in p:
        m, aux = moe.apply_moe(cfg, p["moe"], h)
    else:
        m, aux = apply_mlp(p["mlp"], h, cfg.act), zero_aux()
    x = x + m
    x = shard(x, "dp", "sp", None)
    return x, new_cache, aux


def _apply_rwkv_layer(cfg, p, x, mode, cache=None):
    h = apply_norm(p["ln1"], x)
    if mode == "decode":
        a, tm_state = rwkv6.time_mix_decode(cfg, p["tm"], h, cache["tm"])
    else:
        a, tm_state = rwkv6.time_mix_seq(cfg, p["tm"], h)
    x = x + a
    h = apply_norm(p["ln2"], x)
    if mode == "decode":
        c, cm_state = rwkv6.channel_mix_decode(cfg, p["cm"], h, cache["cm"])
    else:
        c, cm_state = rwkv6.channel_mix_seq(cfg, p["cm"], h)
    x = x + c
    x = shard(x, "dp", "sp", None)
    return x, {"tm": tm_state, "cm": cm_state}


def _apply_mamba_layer(cfg, p, x, mode, cache=None):
    h = apply_norm(p["ln1"], x)
    if mode == "decode":
        m, state = mamba2.mamba2_decode(cfg, p["mamba"], h, cache)
    else:
        m, state = mamba2.mamba2_seq(cfg, p["mamba"], h)
    x = shard(x + m, "dp", "sp", None)
    return x, state


def _apply_shared_block(cfg, p, x, mode, cache=None, pos=None, max_len=0, cp=False):
    h = apply_norm(p["ln1"], x)
    new_cache = None
    if mode == "train":
        a = attn.self_attention(cfg, p["attn"], h)
    elif mode == "prefill":
        a, new_cache = attn.prefill_attention(cfg, p["attn"], h, max_len, cp=cp)
    else:
        a, new_cache = attn.decode_attention(cfg, p["attn"], h, cache, pos, cp=cp)
    x = x + a
    x = x + apply_mlp(p["mlp"], apply_norm(p["ln2"], x), cfg.act)
    return shard(x, "dp", "sp", None), new_cache


# ----------------------------------------------------------------------- Model
class Model:
    """Functional model wrapper: params are explicit pytrees."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.is_hybrid = cfg.family == "hybrid"
        self.is_rwkv = cfg.ssm is not None and cfg.ssm.kind == "rwkv6"
        self.is_mamba = cfg.ssm is not None and cfg.ssm.kind == "mamba2"

    # ------------------------------------------------------------- param defs
    def _layer_defs(self, idx: int) -> dict:
        cfg = self.cfg
        if self.is_rwkv:
            return _rwkv_layer_defs(cfg)
        if self.is_mamba:  # hybrid backbone or pure mamba
            return _mamba_layer_defs(cfg)
        moe_layer = cfg.moe is not None and idx >= cfg.moe.first_k_dense
        return _dense_layer_defs(cfg, moe_layer)

    def n_scan(self) -> int:
        cfg = self.cfg
        if self.is_hybrid:
            return 0
        return cfg.num_layers - (cfg.moe.first_k_dense if cfg.moe else 0)

    def shared_positions(self) -> list[int]:
        cfg = self.cfg
        if not self.is_hybrid or not cfg.attn_every:
            return []
        return [i for i in range(cfg.num_layers) if i % cfg.attn_every == 0]

    def _hybrid_split(self, layers):
        """Split the (L, ...) layer stack into scanned segments + python tail.

        Segment = [shared attn+MLP block, then attn_every mamba layers]; the
        shared block's weights are closure constants, so scanning segments is
        exact and cuts compile time ~attn_every-fold vs an unrolled loop.
        """
        cfg = self.cfg
        k = cfg.attn_every
        n_seg = cfg.num_layers // k
        n_tail = cfg.num_layers - n_seg * k
        seg = jax.tree.map(lambda a: a[: n_seg * k].reshape(n_seg, k, *a.shape[1:]), layers)
        tail = jax.tree.map(lambda a: a[n_seg * k :], layers)
        return seg, tail, n_seg, n_tail

    def param_defs(self) -> dict:
        cfg = self.cfg
        defs: dict[str, Any] = {
            "embed": embed_defs(cfg.vocab_size, cfg.d_model),
            "final_norm": norm_defs(cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            defs["lm_head"] = head_defs(cfg.d_model, cfg.vocab_size)
        if self.is_hybrid:
            defs["layers"] = jax.tree.map(
                lambda pd: stacked(pd, cfg.num_layers),
                self._layer_defs(0),
                is_leaf=lambda x: isinstance(x, PD),
            )
            defs["shared"] = _shared_block_defs(cfg)
        else:
            n_head = cfg.moe.first_k_dense if cfg.moe else 0
            if n_head:
                defs["head_layers"] = {str(i): self._layer_defs(i) for i in range(n_head)}
            defs["layers"] = jax.tree.map(
                lambda pd: stacked(pd, self.n_scan()),
                self._layer_defs(n_head),
                is_leaf=lambda x: isinstance(x, PD),
            )
        return defs

    def init(self, key) -> dict:
        return init_params(self.param_defs(), key, self.cfg.pdtype)

    def pspecs(self):
        return param_specs(self.param_defs())

    def pshapes(self):
        return param_shapes(self.param_defs(), self.cfg.pdtype)

    def param_count(self) -> int:
        from repro.models.params import count_params

        return count_params(self.param_defs())

    # ------------------------------------------------------------ embeddings
    def _inputs_to_hidden(self, params, batch) -> jax.Array:
        cfg = self.cfg
        if cfg.input_mode == "embeds" and "embeds" in batch:
            x = batch["embeds"].astype(cfg.compute_dtype)
        else:
            x = embed_lookup(params["embed"], batch["tokens"], cfg.compute_dtype)
        return shard(x, "dp", "sp", None)

    def _head(self, params, x) -> jax.Array:
        with jax.named_scope("head"):
            p = params.get("lm_head")
            if p is None:  # tied
                p = {"w": params["embed"]["tok"].T}
            return lm_logits(p, x, jnp.float32)

    # ---------------------------------------------------------------- forward
    def forward(self, params, batch, remat: str | None = None):
        """Training forward: returns (final hidden (B,S,D), aux)."""
        cfg = self.cfg
        x = self._inputs_to_hidden(params, batch)

        policy = None
        if remat and remat != "none":
            policy = (
                jax.checkpoint_policies.nothing_saveable
                if remat == "full"
                else jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )

        if self.is_hybrid:
            seg, tail, n_seg, n_tail = self._hybrid_split(params["layers"])
            k = cfg.attn_every

            def seg_body(h, lp):
                h = _apply_shared_block(cfg, params["shared"], h, "train")[0]
                for j in range(k):
                    ljp = jax.tree.map(lambda a: a[j], lp)
                    h = _apply_mamba_layer(cfg, ljp, h, "train")[0]
                return h, None

            body = seg_body
            if policy is not None:
                body = jax.checkpoint(seg_body, policy=policy, prevent_cse=False)
            if n_seg:
                x, _ = jax.lax.scan(body, x, seg)
            if n_tail:
                x = _apply_shared_block(cfg, params["shared"], x, "train")[0]
                for j in range(n_tail):
                    ljp = jax.tree.map(lambda a: a[j], tail)
                    x = _apply_mamba_layer(cfg, ljp, x, "train")[0]
            aux = zero_aux()
        else:
            head_fn = lambda hp, h: _apply_dense_layer(cfg, hp, h, "train")[0]  # noqa: E731
            if policy is not None:
                head_fn = jax.checkpoint(head_fn, policy=policy, prevent_cse=False)
            for i in range(cfg.moe.first_k_dense if cfg.moe else 0):
                x = head_fn(params["head_layers"][str(i)], x)

            def body(carry, lp):
                x, aux = carry
                if self.is_rwkv:
                    x, _ = _apply_rwkv_layer(cfg, lp, x, "train")
                    a = zero_aux()
                else:
                    x, _, a = _apply_dense_layer(cfg, lp, x, "train")
                aux = {k: aux[k] + a[k] for k in aux}
                return (x, aux), None

            if policy is not None:
                body = jax.checkpoint(body, policy=policy, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(body, (x, zero_aux()), params["layers"])
            aux = {k: v / max(self.n_scan(), 1) for k, v in aux.items()}

        x = apply_norm(params["final_norm"], x)
        return x, aux

    def loss(self, params, batch, remat: str | None = None):
        """Next-token CE with sequence-chunked logits (bounds logits memory)."""
        cfg = self.cfg
        x, aux = self.forward(params, batch, remat=remat)
        labels = batch["labels"]  # (B, S), -1 = ignore
        B, S, D = x.shape
        chunk = min(cfg.loss_chunk, S)
        assert S % chunk == 0
        nc = S // chunk
        xs = jnp.moveaxis(x.reshape(B, nc, chunk, D), 1, 0)
        ys = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def chunk_loss(carry, inp):
            xc, yc = inp
            logits = self._head(params, xc)  # (B, chunk, V) fp32
            logz = jax.nn.logsumexp(logits, axis=-1)
            safe = jnp.maximum(yc, 0)
            gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
            valid = (yc >= 0).astype(jnp.float32)
            nll = ((logz - gold) * valid).sum()
            hit = ((jnp.argmax(logits, -1) == yc) * valid).sum()
            t, n, h = carry
            return (t + nll, n + valid.sum(), h + hit), None

        (tot, n, hits), _ = jax.lax.scan(chunk_loss, (0.0, 0.0, 0.0), (xs, ys))
        n = jnp.maximum(n, 1.0)
        ce = tot / n
        loss = ce
        if cfg.moe is not None:
            loss = loss + cfg.moe.aux_loss_coef * (aux["moe_lb_loss"] + aux["moe_z_loss"])
        metrics = {"loss": loss, "ce": ce, "accuracy": hits / n, **aux}
        return loss, metrics

    # ---------------------------------------------------------------- prefill
    def prefill(self, params, batch, max_len: int, cp: bool = False):
        """Returns (last-token logits (B, V), decode-ready cache)."""
        cfg = self.cfg
        x = self._inputs_to_hidden(params, batch)
        B, S, _ = x.shape

        if self.is_hybrid:
            seg, tail, n_seg, n_tail = self._hybrid_split(params["layers"])
            k = cfg.attn_every

            def seg_body(h, lp):
                h, sh = _apply_shared_block(cfg, params["shared"], h, "prefill", max_len=max_len, cp=cp)
                states = []
                for j in range(k):
                    ljp = jax.tree.map(lambda a: a[j], lp)
                    h, st = _apply_mamba_layer(cfg, ljp, h, "prefill")
                    states.append(st)
                stacked_st = jax.tree.map(lambda *a: jnp.stack(a), *states)
                return h, {"shared": sh, "mamba": stacked_st}

            cache = {}
            if n_seg:
                x, seg_caches = jax.lax.scan(seg_body, x, seg)
                cache["seg"] = seg_caches
            if n_tail:
                x, sh = _apply_shared_block(cfg, params["shared"], x, "prefill", max_len=max_len, cp=cp)
                states = []
                for j in range(n_tail):
                    ljp = jax.tree.map(lambda a: a[j], tail)
                    x, st = _apply_mamba_layer(cfg, ljp, x, "prefill")
                    states.append(st)
                cache["tail"] = {"shared": sh, "mamba": tuple(states)}
        else:
            head_caches = {}
            for i in range(cfg.moe.first_k_dense if cfg.moe else 0):
                x, c, _ = _apply_dense_layer(
                    cfg, params["head_layers"][str(i)], x, "prefill", max_len=max_len, cp=cp
                )
                head_caches[str(i)] = c

            def body(x, lp):
                if self.is_rwkv:
                    x, st = _apply_rwkv_layer(cfg, lp, x, "prefill")
                else:
                    x, st, _ = _apply_dense_layer(cfg, lp, x, "prefill", max_len=max_len, cp=cp)
                return x, st

            x, scan_caches = jax.lax.scan(body, x, params["layers"])
            cache = {"layers": scan_caches}
            if head_caches:
                cache["head_layers"] = head_caches

        x = apply_norm(params["final_norm"], x)
        logits = self._head(params, x[:, -1])  # (B, V)
        cache["pos"] = jnp.array(S, jnp.int32)
        return logits, cache

    def init_cache(self, batch_size: int, max_len: int, cp: bool = False) -> dict:
        """Zeroed cache for decode-from-scratch (or dry-run decode lowering)."""
        cfg = self.cfg
        if self.is_hybrid:
            k = cfg.attn_every
            n_seg = cfg.num_layers // k
            n_tail = cfg.num_layers - n_seg * k
            m1 = mamba2.init_mamba2_state(cfg, batch_size)
            a1 = attn.init_attn_cache(cfg, batch_size, max_len, cp=cp)
            cache = {}
            if n_seg:
                cache["seg"] = {
                    "shared": jax.tree.map(lambda a: jnp.broadcast_to(a, (n_seg, *a.shape)), a1),
                    "mamba": jax.tree.map(
                        lambda a: jnp.broadcast_to(a, (n_seg, k, *a.shape)), m1
                    ),
                }
            if n_tail:
                cache["tail"] = {
                    "shared": a1,
                    "mamba": tuple(
                        mamba2.init_mamba2_state(cfg, batch_size) for _ in range(n_tail)
                    ),
                }
        elif self.is_rwkv:
            one = rwkv6.init_rwkv6_state(cfg, batch_size)
            cache = {"layers": jax.tree.map(lambda a: jnp.broadcast_to(a, (self.n_scan(), *a.shape)), one)}
        else:
            n_head = cfg.moe.first_k_dense if cfg.moe else 0
            one = {"kv": attn.init_attn_cache(cfg, batch_size, max_len, cp=cp)}
            cache = {
                "layers": jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (self.n_scan(), *a.shape)).astype(a.dtype), one
                )
            }
            if n_head:
                cache["head_layers"] = {
                    str(i): {"kv": attn.init_attn_cache(cfg, batch_size, max_len, cp=cp)}
                    for i in range(n_head)
                }
        cache["pos"] = jnp.array(0, jnp.int32)
        return cache

    # ----------------------------------------------------------------- decode
    def decode_step(self, params, cache, tokens, cp: bool = False):
        """One autoregressive step. tokens: (B, 1) int32 -> (logits (B,V), cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        x = embed_lookup(params["embed"], tokens, cfg.compute_dtype)

        if self.is_hybrid:
            seg, tail, n_seg, n_tail = self._hybrid_split(params["layers"])
            k = cfg.attn_every

            def seg_body(h, inp):
                lp, c = inp
                h, sh = _apply_shared_block(
                    cfg, params["shared"], h, "decode", cache=c["shared"], pos=pos, cp=cp
                )
                states = []
                for j in range(k):
                    ljp = jax.tree.map(lambda a: a[j], lp)
                    cj = jax.tree.map(lambda a: a[j], c["mamba"])
                    h, st = _apply_mamba_layer(cfg, ljp, h, "decode", cache=cj)
                    states.append(st)
                stacked_st = jax.tree.map(lambda *a: jnp.stack(a), *states)
                return h, {"shared": sh, "mamba": stacked_st}

            new_cache = {}
            if n_seg:
                x, new_seg = jax.lax.scan(seg_body, x, (seg, cache["seg"]))
                new_cache["seg"] = new_seg
            if n_tail:
                x, sh = _apply_shared_block(
                    cfg, params["shared"], x, "decode", cache=cache["tail"]["shared"], pos=pos, cp=cp
                )
                states = []
                for j in range(n_tail):
                    ljp = jax.tree.map(lambda a: a[j], tail)
                    x, st = _apply_mamba_layer(
                        cfg, ljp, x, "decode", cache=cache["tail"]["mamba"][j]
                    )
                    states.append(st)
                new_cache["tail"] = {"shared": sh, "mamba": tuple(states)}
        else:
            new_head = {}
            for i in range(cfg.moe.first_k_dense if cfg.moe else 0):
                x, c, _ = _apply_dense_layer(
                    cfg,
                    params["head_layers"][str(i)],
                    x,
                    "decode",
                    cache=cache["head_layers"][str(i)],
                    pos=pos,
                    cp=cp,
                )
                new_head[str(i)] = c

            def body(x, inp):
                lp, lc = inp
                if self.is_rwkv:
                    x, st = _apply_rwkv_layer(cfg, lp, x, "decode", cache=lc)
                else:
                    x, st, _ = _apply_dense_layer(cfg, lp, x, "decode", cache=lc, pos=pos, cp=cp)
                return x, st

            x, scan_caches = jax.lax.scan(body, x, (params["layers"], cache["layers"]))
            new_cache = {"layers": scan_caches}
            if new_head:
                new_cache["head_layers"] = new_head

        x = apply_norm(params["final_norm"], x)
        logits = self._head(params, x[:, 0])
        new_cache["pos"] = pos + 1
        return logits, new_cache

    # ----------------------------------------------------- cache sharding spec
    def cache_pspecs(self, cp: bool = False):
        """PartitionSpec tree matching init_cache structure (for pjit shardings).

        Leaves are PartitionSpec (resolved under the current sharding rules);
        built by name-mapping the per-component logical spec dicts.
        """
        from repro.parallel.axes import logical_spec

        def _is_axes(t) -> bool:
            # a logical-axes tuple: entries are names, None, or tuples of names
            return isinstance(t, tuple) and all(
                isinstance(n, (str, type(None)))
                or (isinstance(n, tuple) and all(isinstance(m, str) for m in n))
                for n in t
            )

        def to_p(spec_tree):
            return jax.tree.map(lambda names: logical_spec(*names), spec_tree, is_leaf=_is_axes)

        cfg = self.cfg
        if self.is_hybrid:
            m = mamba2.mamba2_state_specs(cfg)
            a = attn.attn_cache_specs(cfg, cp=cp)
            is_t = lambda t: isinstance(t, tuple)  # noqa: E731
            k = cfg.attn_every
            n_seg = cfg.num_layers // k
            n_tail = cfg.num_layers - n_seg * k
            cache = {}
            if n_seg:
                cache["seg"] = {
                    "shared": to_p(jax.tree.map(lambda t: (None, *t), a, is_leaf=is_t)),
                    "mamba": to_p(jax.tree.map(lambda t: (None, None, *t), m, is_leaf=is_t)),
                }
            if n_tail:
                cache["tail"] = {
                    "shared": to_p(a),
                    "mamba": tuple(to_p(m) for _ in range(n_tail)),
                }
        elif self.is_rwkv:
            s = rwkv6.rwkv6_state_specs(cfg)
            stacked_s = jax.tree.map(
                lambda t: (None, *t), s, is_leaf=lambda t: isinstance(t, tuple)
            )
            cache = {"layers": to_p(stacked_s)}
        else:
            a = attn.attn_cache_specs(cfg, cp=cp)
            stacked_a = {
                "kv": to_p(
                    jax.tree.map(lambda t: (None, *t), a, is_leaf=lambda t: isinstance(t, tuple))
                )
            }
            cache = {"layers": stacked_a}
            n_head = cfg.moe.first_k_dense if cfg.moe else 0
            if n_head:
                cache["head_layers"] = {str(i): {"kv": to_p(a)} for i in range(n_head)}
        cache["pos"] = logical_spec()
        return cache

    def cache_shapes(self, batch_size: int, max_len: int, cp: bool = False):
        """ShapeDtypeStruct tree of the decode cache (no allocation; AOT)."""
        return jax.eval_shape(lambda: self.init_cache(batch_size, max_len, cp=cp))
