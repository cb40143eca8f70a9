"""Two-float arithmetic for the float32 trace of the score kernels.

The fitness exponential is DEFINED in float64 and rounded once to
float32 (structs/funcs.py ``_pow10``): ``float32(10 ** (1 - used/cap))``
with the share and the power taken in float64.  A float32 trace cannot
call ``pow`` for that: the TPU's float32 ``pow`` is off the definition
by up to 5e-6 (relative) and its division by 1e-7, enough to reorder
near-tied candidates.  So the float32 trace carries the free share and
the exponential as an unevaluated sum ``hi + lo`` of two float32 arrays
(about 48 significant bits) and rounds once at the end.  The rest of a
score — the sum of the two exponentials, ``20 - sum``, ``/ 18``, the
anti-affinity and penalty terms, the node-affinity term, the spread
boost and the mean — is defined in float64 too, so it stays a pair all
the way to the walk's maximum (ops/score.py ``ScoreList``): the pair
operations below the exponential (``exact_sum``, ``add``, ``add_f``,
``mul``, ``quotient``, ``clip``, ``at_most``) are the ones that tail
needs.  A float64 input that is no float32 (a spread's desired count
of 1.8, a weight of 60/95) reaches the trace as the pair the host
splits it into (``split64``).

Only float32 ``+``, ``-`` and ``*`` carry the result, and they are
correctly rounded on the CPU and on the TPU alike.  Every product whose
rounding would matter is taken of 12-bit halves (the significand split
by a bit mask) and is therefore exact, so a backend that contracts a
multiplication into the following addition (XLA's CPU backend) and one
that cannot (the TPU) give the same bits.  The backend's division only
seeds a quotient that is then refined on its exact remainder.

A pair is a tuple ``(hi, lo)`` with ``hi == fl(hi + lo)``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pair = Tuple[jnp.ndarray, jnp.ndarray]

F32 = np.float32


def _const(value: float) -> Tuple[np.float32, np.float32]:
    hi = F32(value)
    return hi, F32(value - float(hi))


def split64(x) -> Tuple[np.ndarray, np.ndarray]:
    """A float64 numpy array as the pair of float32 arrays nearest to
    it (about 48 of its 53 significant bits), on the host: ``hi`` is
    what narrowing the array to a float32 trace gives, ``lo`` what that
    narrowing drops."""
    x = np.asarray(x, np.float64)
    hi = x.astype(F32)
    return hi, (x - hi).astype(F32)


def _two_sum(a, b) -> Pair:
    """s + e == a + b exactly, s = fl(a + b) (Knuth)."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fast_two_sum(a, b) -> Pair:
    """``_two_sum`` where |a| >= |b| or a == 0 (Dekker)."""
    s = a + b
    return s, b - (s - a)


def _split(a) -> Pair:
    """a == hi + lo exactly, each half of at most 12 significant bits."""
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.int32(-4096), jnp.float32)
    return hi, a - hi


def _two_prod(a, b) -> Pair:
    """a * b as a pair, to 2^-47 of it: the four products of halves are
    exact, and only the three smallest are summed in float32."""
    ah, al = _split(a)
    bh, bl = _split(b)
    mid, mid_e = _two_sum(ah * bl, al * bh)
    top, top_e = _two_sum(ah * bh, mid)
    return _fast_two_sum(top, top_e + (mid_e + al * bl))


def add(x: Pair, y: Pair) -> Pair:
    s, e = _two_sum(x[0], y[0])
    return _fast_two_sum(s, e + (x[1] + y[1]))


def add_f(x: Pair, b) -> Pair:
    s, e = _two_sum(x[0], b)
    return _fast_two_sum(s, e + x[1])


def mul(x: Pair, y: Pair) -> Pair:
    """``x * y`` to about 2^-46 of it."""
    p, e = _two_prod(x[0], y[0])
    return _fast_two_sum(p, e + (x[0] * y[1] + x[1] * y[0]))


def exact_sum(a, b) -> Pair:
    """``a + b`` of two float32 arrays as a pair, exactly."""
    return _two_sum(a, b)


def neg(x: Pair) -> Pair:
    return -x[0], -x[1]


def quotient(x: Pair, d) -> Pair:
    """``x / d`` for a ``d`` other than 0, one float32 array (a whole
    number, say) or a pair, to about 2^-46: the backend's quotient by
    ``d``'s ``hi``, then two corrections by the quotient of the
    remainder ``x - q * d``, which is taken to the pair.  A quotient
    by one float32 that a pair holds exactly comes out exact, and
    ``d == 1`` hands ``x`` back."""
    d_hi, d_lo = d if isinstance(d, tuple) else (d, None)
    q = x[0] / d_hi
    out = (q, jnp.zeros_like(q))
    rem = x
    for _ in range(2):
        p, e = _two_prod(q, d_hi)
        if d_lo is not None:
            e = e + q * d_lo
        rem = add(rem, (-p, -e))
        q = rem[0] / d_hi
        out = add_f(out, q)
    return out


def clip(x: Pair, low: float, high: float) -> Pair:
    """``x`` held to [low, high], two float32 constants."""
    over = (x[0] > high) | ((x[0] == high) & (x[1] > 0.0))
    under = (x[0] < low) | ((x[0] == low) & (x[1] < 0.0))
    hi = jnp.where(over, F32(high), jnp.where(under, F32(low), x[0]))
    return hi, jnp.where(over | under, F32(0.0), x[1])


def at_most(x: Pair, bound: float):
    """``x <= bound`` for a float32 constant: ``hi`` decides, and
    ``lo``'s sign where ``hi`` sits on the bound."""
    return (x[0] < bound) | ((x[0] == bound) & (x[1] <= 0.0))


def free_share(after, cap) -> Pair:
    """``1 - after / cap`` for float32 columns of whole numbers
    (``cap`` > 0), to about 2^-47 (``quotient``)."""
    q = quotient((after, jnp.zeros_like(after)), cap)
    return add_f(neg(q), F32(1.0))


# log10(2) in three parts, the first two of 12 bits each, so that
# n * part is exact for |n| < 2^12 (Cody and Waite's reduction)
_LOG10_2 = math.log10(2.0)
_L1 = F32(math.floor(_LOG10_2 * 2.0**13) / 2.0**13)
_L2 = F32(math.floor((_LOG10_2 - float(_L1)) * 2.0**25) / 2.0**25)
_L3 = F32(_LOG10_2 - float(_L1) - float(_L2))
_LOG2_10 = F32(math.log2(10.0))
_LN10 = _const(math.log(10.0))
# e^t = sum t^k / k!, |t| <= ln(2)/2: the terms of degree 8..12 weigh
# under 2^-27 of the sum, so float32 does for them; degree 13 and up
# weigh under 2^-52
_PAIR_TERMS = 8
_LAST_TERM = 12
_INV_FACT = [1.0 / math.factorial(k) for k in range(_LAST_TERM + 1)]


def pow10(x: Pair) -> Pair:
    """10^x as a pair, relative error about 2^-46; its ``hi`` is the
    float32 nearest to 10^x unless 10^x lies that close to the midpoint
    of two float32 neighbours."""
    n = jnp.clip(jnp.round(x[0] * _LOG2_10), -126.0, 127.0)
    # r = x - n * log10(2): 10^x = 2^n * 10^r, |r| <= log10(2) / 2
    r = add_f(add_f(add_f(x, -n * _L1), -n * _L2), -n * _L3)
    t = mul(r, _LN10)  # 10^r = e^t
    tail = jnp.full_like(t[0], F32(_INV_FACT[_LAST_TERM]))
    for k in range(_LAST_TERM - 1, _PAIR_TERMS - 1, -1):
        tail = tail * t[0] + F32(_INV_FACT[k])
    p = (tail, jnp.zeros_like(tail))
    for k in range(_PAIR_TERMS - 1, -1, -1):
        p = add(mul(p, t), _const(_INV_FACT[k]))
    # times 2^n, exact: the exponent field written directly
    scale = jax.lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32
    )
    return p[0] * scale, p[1] * scale


def pow10_free(after, cap):
    """``float32(10 ** (1 - after / cap))`` as the float64 definition
    rounds it, from float32 columns."""
    return pow10(free_share(after, cap))[0]
