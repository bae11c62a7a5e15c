"""The one interpret-mode rule every Pallas kernel package follows."""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """True only on the CPU platform: kernels run under the Pallas
    interpreter there and compiled everywhere else, so a chip run never
    falls back to the interpreter."""
    return jax.default_backend() == "cpu"
