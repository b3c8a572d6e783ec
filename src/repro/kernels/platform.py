"""Kernel mode from the lowering platform.

Every Pallas kernel in this package is built twice and staged out with
`jax.lax.platform_dependent`: a Mosaic call when the program lowers for
a TPU, the Pallas interpreter for every other platform (the CPU tests).
The choice is made when the program is lowered, so an AOT compile for
a described TPU gets the Mosaic kernel (a `tpu_custom_call` in its HLO)
while the same jitted function runs interpreted on the CPU.  There is
no option to select the mode: a TPU never runs the interpreter.
"""

from __future__ import annotations

from typing import Callable

import jax


def pallas_on_platform(build: Callable[[bool], Callable], *args):
    """`build(interpret)` returns the `pl.pallas_call(...)` callable for
    that mode; apply it to `args` in the mode of the lowering platform."""
    return jax.lax.platform_dependent(
        *args, tpu=build(False), default=build(True)
    )
