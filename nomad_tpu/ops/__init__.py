"""JAX kernels: the vectorized scoring backend.

`constraints.py` compiles constraints/affinities/spreads into boolean or
float lookup tables over interned column vocabularies (exact reference
operator semantics evaluated host-side over the tiny vocab; the device
does only `lut[codes]` gathers).  `score.py` is the jitted score kernel +
deterministic limited-walk selection that reproduces the reference's
GenericStack.Select bit-for-bit.  `batch.py` scans/vmaps the kernel over
picks and evals for throughput.
"""
from ..backend import ensure_compile_cache

# every kernel module lives in this package, so every path that jits
# passes through here first: the persistent compile cache is placed
# before anything can compile
ensure_compile_cache()

from .score import score_and_select, ScoreInputs  # noqa: E402,F401
from .constraints import MaskCompiler  # noqa: E402,F401
