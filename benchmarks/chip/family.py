"""Find a configuration's family: everything of the benchmark that is specific
to one architecture, as a file of its own.

A configuration file names its family with `"reference": "<name>"`, and the
family is `families/<name>.py` under the harness's data directory. A new
architecture brings its family as a new file, and a configuration names it;
no other file of the harness changes. A family module holds:

- `COVERS`: {program-config field: value} that the family implements; the
  harness refuses a configuration whose program config differs in any;
- `SCOPES`: the named scopes of the family's programs, a part written under
  its scope (`attn/core`); `scopes.scope_map` maps the compiled HLO by them;
- `layout(cfg)`: the program's parameter tree, {path: (shape, dtype name)},
  from which `weights.py` draws the weights;
- `scored_logits(cfg, seed, seqs, first, mode="f32")`: the plain float32
  reference at precision `highest`, with `mode="fp8"` the control;
- `prefill_flops(cfg, batch, prompt)`, `decode_flops(cfg, batch, pos)`,
  `prefill_bytes(...)`, `decode_bytes(...)`: the operations and bytes one
  prefill and one decode step need (`flops.py`).

What the family implements of a cut configuration, such as a sliced
vocabulary or a chip's share of the experts, is the family's and its
configuration's business: the harness hands both the configuration's dict.

A configuration file may give a section of the program's config as a mapping,
such as `"moe": {"num_experts": 8, "top_k": 6}`: the program takes it field by
field onto the arch's own section (`run.model_config`), and `COVERS` is held
against the section so built. A family reads a section as the file gives it,
`cfg["moe"]["top_k"]` in `layout`, and its jitted reference reads the
section's numbers and names under dotted names, `"moe.top_k"`, in the static
argument (`reference.static`); so the file states every field of a section
that its family reads. The keys in `META` are no fields of the program's
config, and no sections.
"""

from __future__ import annotations

import importlib.util
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]*")

_loaded: dict = {}  # name -> the module `load` last found for it

# keys of a configuration file that are not fields of the program's config
META = ("source", "arch", "deployment", "reduced", "assumed", "departures", "compile_rehearsal",
        "reference")


def sections(cfg: dict) -> dict[str, dict]:
    """The program-config sections a configuration file gives, {name: {field: value}}."""
    return {k: v for k, v in cfg.items() if k not in META and isinstance(v, dict)}


def load_file(path: Path, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(base: Path, cfg: dict):
    """The family `cfg` names, from `base/families/`. A configuration that names
    none, or one that has no file, is an error: there is no default family."""
    name = cfg.get("reference")
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"the configuration names no family: it needs \"reference\": \"<name>\" "
                         f"for a file families/<name>.py, and has {name!r}")
    path = (base / "families" / f"{name}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"the configuration names family {name!r}, and {path} is missing")
    mod = _loaded.get(name)
    if mod is None or Path(mod.__file__) != path:
        mod = _loaded[name] = load_file(path, f"family_{name}")
    return mod


def of(cfg: dict):
    """The family of `cfg` as `load` last found it, else from the harness's own
    `families/`."""
    return _loaded.get(cfg.get("reference")) or load(HERE, cfg)
