"""TPU kernels for the compute hot spots the paper optimizes.

The paper's perf-critical layers: (a) the SIMD integer codec (its core
contribution) -> ``bitpack``; (b) bitmap popcounts (§3.1) -> ``popcount``;
(c) the SIMD-optimized SpMV inner loop (§6) -> ``spmv`` (ELL frontier
expansion, push and pull directions).  Beyond-paper: ``quant`` (int8 block
quantization for gradient/payload compression).  ``bitpack``, ``popcount``
and ``quant`` each ship a ``pl.pallas_call`` + BlockSpec implementation,
an ``ops.py`` jit'd wrapper and a ``ref.py`` pure-jnp oracle; tests sweep
shapes/dtypes/densities against the oracles in interpret mode, and
tests/test_chip_compile.py compiles them for v5e.  ``spmv`` is an XLA
gather: Mosaic cannot lower its bitmap probe (see its ``ref`` module).
"""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    """Shared ``interpret=`` default for every Pallas entry point.

    Compiled on TPU, interpreted everywhere else — kernels resolve the
    backend once, here, instead of each entry point hard-coding a mode.
    Entry points take ``interpret: bool | None = None`` and resolve ``None``
    through this helper; an explicit bool still overrides (tests force
    interpret mode regardless of backend).
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve an entry point's ``interpret`` argument (None -> backend default)."""
    return interpret_default() if interpret is None else interpret
