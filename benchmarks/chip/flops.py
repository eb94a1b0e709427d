"""Operations and bytes the dense decoder's work needs, from its shapes.

Only needed work counts: causal attention as the (S + 1) * S / 2 query-key
pairs it has, a decode step's keys and values of valid positions only (not the
whole cache), the head where logits are used. A program that skips masked work
then reads as a higher share of its roofline, never as more than all of it.
All counts take a configuration file's dict.
"""

from __future__ import annotations


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


def wave_flops(cfg: dict, slots: int, prompt: int, decode_steps: int) -> float:
    """A wave: one prefill, then decode steps at positions prompt, prompt + 1, ..."""
    return prefill_flops(cfg, slots, prompt) + sum(
        decode_flops(cfg, slots, prompt + j) for j in range(decode_steps))


def wave_roofline_s(cfg: dict, slots: int, prompt: int, decode_steps: int, peak: dict) -> float:
    """Least time of a wave on a chip: each program bound by its operations or
    its bytes, whichever takes longer."""
    def least(f, b):
        return max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])

    t = least(prefill_flops(cfg, slots, prompt), prefill_bytes(cfg, slots, prompt))
    for j in range(decode_steps):
        t += least(decode_flops(cfg, slots, prompt + j), decode_bytes(cfg, slots, prompt + j))
    return t
