"""The dense decoder family: what the benchmark knows of one architecture.

Pre-norm blocks of LayerNorm -> grouped-query causal attention with rotary
positions (rotate-half convention) -> residual -> LayerNorm -> plain MLP (tanh
GELU or squared ReLU) -> residual; a final LayerNorm and the head, untied or
the embedding's transpose where `tie_embeddings` is set. A configuration file
names this family with `"reference": "dense"`; the harness finds it by that
name (`family.py`). Departures from the published models are listed in each
configuration file.

- `COVERS`: the program-config fields and values this family implements; the
  harness refuses a configuration whose program config differs in any.
- `SCOPES`: the named scopes of the programs, a part written under its scope
  (`attn/core`), which `scopes.scope_map` reads from the compiled HLO.
- `layout(cfg)`: the parameter tree the program reads, {path: (shape, dtype
  name)}; the weights are drawn by `weights.py` from it.
- `scored_logits(cfg, seed, seqs, first, mode)`: the plain float32 reference,
  every matrix product at precision `highest`, imports nothing of the program
  and draws its weights from the seed itself, layer by layer; attention is
  computed in blocks of query rows (`reference.attention`), so that it fits
  on one chip once the program's state is freed. `mode="fp8"` is the
  control: every matrix product takes its operands rounded to float8 e4m3
  with one scale per tensor, the precision below the bfloat16 the
  configurations state.
- `prefill_flops`, `decode_flops`, `prefill_bytes`, `decode_bytes`: the
  operations and bytes one prefill and one decode step need, from the shapes.
  Only needed work counts: causal attention as the (S + 1) * S / 2 query-key
  pairs it has, a decode step's keys and values of valid positions only (not
  the whole cache), the head where logits are used. A program that skips
  masked work then reads as a higher share of its roofline, never as more
  than all of it. `flops.py` sums them over a wave.

Every function takes a configuration file's dict.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import reference
import weights
from reference import act, einsum, layer_norm

COVERS = {"family": "dense", "norm": "layernorm", "gated_mlp": False, "qk_norm": False,
          "sliding_window": None, "moe": None, "ssm": None, "attn_every": None,
          "input_mode": "tokens"}
SCOPES = ("embed", "norm", "attn", "attn/qkv", "attn/kv_write", "attn/core", "attn/out", "mlp",
          "head")
LAYER_NAMES = ("ln1/scale", "ln1/bias", "attn/wq", "attn/wk", "attn/wv", "attn/wo",
               "ln2/scale", "ln2/bias", "mlp/wi", "mlp/wo")


def layout(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """The parameter tree: LayerNorm, GQA attention, plain MLP, and a head of
    its own unless `tie_embeddings`."""
    L, d, V, ff = cfg["num_layers"], cfg["d_model"], cfg["vocab_size"], cfg["d_ff"]
    q, kv = cfg["num_heads"] * cfg["head_dim"], cfg["num_kv_heads"] * cfg["head_dim"]
    p, f = cfg["param_dtype"], "float32"
    out = {
        "embed/tok": ((V, d), p),
        "final_norm/scale": ((d,), f),
        "final_norm/bias": ((d,), f),
    }
    if not cfg["tie_embeddings"]:
        out["lm_head/w"] = ((d, V), p)
    per_layer = {
        "ln1/scale": ((d,), f), "ln1/bias": ((d,), f),
        "attn/wq": ((d, q), p), "attn/wk": ((d, kv), p),
        "attn/wv": ((d, kv), p), "attn/wo": ((q, d), p),
        "ln2/scale": ((d,), f), "ln2/bias": ((d,), f),
        "mlp/wi": ((d, ff), p), "mlp/wo": ((ff, d), p),
    }
    for name, (shape, dt) in per_layer.items():
        out[f"layers/{name}"] = ((L, *shape), dt)
    return out


# ---------------------------------------------------------------- reference
def block_fn(cfg, p, x, mode):
    h = layer_norm(x, p["ln1/scale"], p["ln1/bias"])
    x = x + reference.attention(cfg, h, p["attn/wq"], p["attn/wk"], p["attn/wv"], p["attn/wo"],
                                mode)
    h = layer_norm(x, p["ln2/scale"], p["ln2/bias"])
    h = act(cfg["act"], einsum("ntd,df->ntf", h, p["mlp/wi"], mode))
    return x + einsum("ntf,fd->ntd", h, p["mlp/wo"], mode)


@partial(jax.jit, static_argnums=(0, 3, 4))
def _scored_logits(cfg_items, key, seqs, first, mode):
    cfg = dict(cfg_items)
    lay = layout(cfg)
    emb = weights.leaf(key, "embed/tok", *lay["embed/tok"], cfg["tie_embeddings"])
    x = jnp.take(emb, seqs, axis=0).astype(jnp.float32)

    def body(x, i):
        p = {n: weights.layer_leaf(key, f"layers/{n}", lay[f"layers/{n}"][0][1:],
                                   lay[f"layers/{n}"][1], i) for n in LAYER_NAMES}
        return block_fn(cfg, p, x, mode), None

    x, _ = jax.lax.scan(body, x, jnp.arange(cfg["num_layers"], dtype=jnp.uint32))
    fn = {n: weights.leaf(key, f"final_norm/{n}", *lay[f"final_norm/{n}"])
          for n in ("scale", "bias")}
    h = layer_norm(x[:, first:], fn["scale"], fn["bias"])
    if cfg["tie_embeddings"]:
        head = emb.T
    else:
        head = weights.leaf(key, "lm_head/w", *lay["lm_head/w"])
    return einsum("ntd,dv->ntv", h, head.astype(jnp.float32), mode)


def scored_logits(cfg: dict, seed: int, seqs, first: int, mode: str = "f32"):
    """Logits (N, T - first, V) at positions first..T-1 of the token rows
    `seqs` (N, T), each row read from position 0."""
    with jax.default_matmul_precision("highest"):
        return _scored_logits(reference.static(cfg), weights.seed_key(seed), jnp.asarray(seqs),
                              first, mode)


# ------------------------------------------------------------- work counts
def layer_matmul_params(cfg: dict) -> int:
    d, ff = cfg["d_model"], cfg["d_ff"]
    q, kv = cfg["num_heads"] * cfg["head_dim"], cfg["num_kv_heads"] * cfg["head_dim"]
    return d * q + 2 * d * kv + q * d + 2 * d * ff


def head_params(cfg: dict) -> int:
    return cfg["d_model"] * cfg["vocab_size"]


def tables(cfg: dict) -> int:
    """Matrices of vocabulary rows stored: the embedding, and the head unless tied."""
    return 1 if cfg["tie_embeddings"] else 2


def params(cfg: dict) -> int:
    """Every parameter: layers with their norms, the embedding, the final norm
    and the head where it is not tied."""
    per_layer = layer_matmul_params(cfg) + 4 * cfg["d_model"]
    return cfg["num_layers"] * per_layer + tables(cfg) * head_params(cfg) + 2 * cfg["d_model"]


def weight_bytes(cfg: dict) -> int:
    """Bytes of the weights as stored: matrices in the parameter dtype, norms in float32."""
    pb = 2 if cfg["param_dtype"] == "bfloat16" else 4
    L, d = cfg["num_layers"], cfg["d_model"]
    mats = L * layer_matmul_params(cfg) + tables(cfg) * head_params(cfg)
    return pb * mats + 4 * (4 * L * d + 2 * d)


def kv_bytes_per_token(cfg: dict) -> int:
    cb = 2 if cfg["dtype"] == "bfloat16" else 4
    return cfg["num_layers"] * 2 * cfg["num_kv_heads"] * cfg["head_dim"] * cb


def attention_pairs_flops(cfg: dict) -> int:
    """Operations of one query-key pair over all layers: scores and weighted values."""
    return cfg["num_layers"] * 4 * cfg["num_heads"] * cfg["head_dim"]


def prefill_flops(cfg: dict, batch: int, prompt: int) -> float:
    """One prefill of `batch` prompts: every token through the layers, causal
    attention, and the head at the last position only."""
    mm = 2 * cfg["num_layers"] * layer_matmul_params(cfg) * prompt
    att = attention_pairs_flops(cfg) * prompt * (prompt + 1) / 2
    return batch * (mm + att + 2 * head_params(cfg))


def decode_flops(cfg: dict, batch: int, pos: int) -> float:
    """One decode step of the token at position `pos` (0-based), which attends
    to pos + 1 keys."""
    mm = 2 * (cfg["num_layers"] * layer_matmul_params(cfg) + head_params(cfg))
    return batch * (mm + attention_pairs_flops(cfg) * (pos + 1))


def step_weight_bytes(cfg: dict, tokens: int) -> float:
    """Weight bytes one program reads for `tokens` tokens: every matrix once,
    except an untied embedding, of which only the tokens' rows are read."""
    eb = 2 if cfg["param_dtype"] == "bfloat16" else 4
    rows = eb * cfg["d_model"] * tokens
    untied_table = 0 if cfg["tie_embeddings"] else eb * head_params(cfg)
    return weight_bytes(cfg) - untied_table + rows


def prefill_bytes(cfg: dict, batch: int, prompt: int) -> float:
    """Weights read once, keys and values written."""
    return step_weight_bytes(cfg, batch * prompt) + kv_bytes_per_token(cfg) * batch * prompt


def decode_bytes(cfg: dict, batch: int, pos: int) -> float:
    """Weights read once, the valid keys and values read, the new ones written."""
    return step_weight_bytes(cfg, batch) + kv_bytes_per_token(cfg) * batch * (pos + 1)
