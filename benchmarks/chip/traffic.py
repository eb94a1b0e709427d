"""The one generator of the benchmark's traffic, read from a mix's data file.

A mix is `traffic/<name>.json`. Its `kind` says which loop drives the program:

- `waves`: closed-loop serving. A wave is one `ServeEngine.serve()` call on
  exactly `slots` requests, each a prompt of `prompt_len` random token ids and
  `new_tokens` greedy tokens, in a cache of `max_len` per slot; the next wave is
  due when the previous one returns. `check_requests` finished requests are
  compared with the reference after the window. With `"topic_ids": n` the
  prompts are skewed by topic: each wave draws one set of `n` distinct ids of
  the vocabulary from the seed, and every prompt of the wave draws its tokens
  from that set; `n` is a whole number from 1 to the vocabulary's size.
  Without it, every token is drawn from the whole vocabulary.

Every number drawn comes from `--seed` and the wave index, so the same
seed gives the same inputs, and every seed gives the same shapes.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

KINDS = {
    "waves": ("slots", "prompt_len", "new_tokens", "max_len", "check_requests"),
}


def load(base: Path, name: str) -> dict:
    mix = json.loads((base / "traffic" / f"{name}.json").read_text())
    need = KINDS.get(mix.get("kind"))
    if need is None:
        raise ValueError(f"traffic {name}: unknown kind {mix.get('kind')!r}")
    missing = [k for k in need if k not in mix]
    if missing:
        raise ValueError(f"traffic {name}: missing {missing}")
    n = mix.get("topic_ids")
    if "topic_ids" in mix and (not isinstance(n, int) or isinstance(n, bool) or n < 1):
        raise ValueError(f"traffic {name}: topic_ids {n!r} is not a whole number of 1 or more")
    return mix


def prompts(seed: int, wave: int, slots: int, prompt_len: int, vocab: int,
            topic_ids: int | None = None) -> np.ndarray:
    """The prompts of one wave: (slots, prompt_len) token ids, with
    `topic_ids` all drawn from one set of that many ids."""
    rng = np.random.default_rng([seed, 0, wave])
    if topic_ids is None:
        return rng.integers(0, vocab, size=(slots, prompt_len), dtype=np.int32)
    if not 1 <= topic_ids <= vocab:
        raise ValueError(f"topic_ids {topic_ids} is not within 1..{vocab}, the vocabulary")
    topics = rng.choice(vocab, size=topic_ids, replace=False).astype(np.int32)
    return topics[rng.integers(0, topic_ids, size=(slots, prompt_len))]


def check_sample(seed: int, n_requests: int, k: int) -> list[int]:
    """Which finished requests the reference reads: `k` of them, from the seed."""
    rng = np.random.default_rng([seed, 1])
    return sorted(rng.choice(n_requests, size=min(k, n_requests), replace=False).tolist())
