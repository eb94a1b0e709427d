"""Operations and bytes a wave of serving needs, from its shapes.

A wave is one prefill, then decode steps; each program is counted by the
configuration's family (`families/<name>.py`: `prefill_flops`,
`decode_flops`, `prefill_bytes`, `decode_bytes`), which counts only needed
work. All counts take a configuration file's dict.
"""

from __future__ import annotations

import family


def wave_flops(cfg: dict, slots: int, prompt: int, decode_steps: int) -> float:
    """A wave: one prefill, then decode steps at positions prompt, prompt + 1, ..."""
    fam = family.of(cfg)
    return fam.prefill_flops(cfg, slots, prompt) + sum(
        fam.decode_flops(cfg, slots, prompt + j) for j in range(decode_steps))


def wave_roofline_s(cfg: dict, slots: int, prompt: int, decode_steps: int, peak: dict) -> float:
    """Least time of a wave on a chip: each program bound by its operations or
    its bytes, whichever takes longer."""
    fam = family.of(cfg)

    def least(f, b):
        return max(f / peak["bf16_flops"], b / peak["hbm_bytes_per_s"])

    t = least(fam.prefill_flops(cfg, slots, prompt), fam.prefill_bytes(cfg, slots, prompt))
    for j in range(decode_steps):
        pos = prompt + j
        t += least(fam.decode_flops(cfg, slots, pos), fam.decode_bytes(cfg, slots, pos))
    return t
