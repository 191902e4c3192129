"""Which Pallas backend a kernel call gets: Mosaic on a TPU, the
interpreter anywhere else.

The platform is ``tpu`` or it is not; there is no alias list and no
environment switch. A test that compiles a kernel for a described
(unattached) chip passes ``interpret=False`` itself, or patches
``default_interpret`` when it compiles a whole program.
"""

from __future__ import annotations

from typing import Optional

import jax


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)
