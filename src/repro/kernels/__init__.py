# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

import jax


def check_backend(interpret: bool) -> None:
    """The Pallas kernels here are written for the TPU. Elsewhere a caller must
    ask for the Pallas interpreter explicitly (`interpret=True`, tests only);
    there is no silent fallback that would time the interpreter."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas TPU kernel called on backend {jax.default_backend()!r}; "
            "pass interpret=True to run it in the Pallas interpreter"
        )
