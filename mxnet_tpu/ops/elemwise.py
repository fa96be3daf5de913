"""Elementwise unary/binary/scalar operators.

Covers the reference's ``src/operator/tensor/elemwise_unary_op_basic.cc``,
``elemwise_binary_op_basic.cc``, ``elemwise_binary_broadcast_op_*.cc`` and
``elemwise_binary_scalar_op_*.cc`` families.  Every op is a pure jnp
expression — XLA fuses chains of these into single kernels, which is the
TPU-native version of the reference's expression-template fusion (mshadow).
"""

from __future__ import annotations

import jax.numpy as jnp
import jax

from .registry import register_op, alias

# ---------------------------------------------------------------------------
# unary
# ---------------------------------------------------------------------------

_UNARY = {
    "abs": jnp.abs,
    "sign": jnp.sign,
    "rint": jnp.rint,
    "round": jnp.round,
    "ceil": jnp.ceil,
    "floor": jnp.floor,
    "trunc": jnp.trunc,
    "fix": jnp.trunc,
    "square": jnp.square,
    "sqrt": jnp.sqrt,
    "rsqrt": lambda x: jax.lax.rsqrt(x),
    "cbrt": jnp.cbrt,
    "rcbrt": lambda x: 1.0 / jnp.cbrt(x),
    "exp": jnp.exp,
    "log": jnp.log,
    "log10": jnp.log10,
    "log2": jnp.log2,
    "log1p": jnp.log1p,
    "expm1": jnp.expm1,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "arctan": jnp.arctan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "arcsinh": jnp.arcsinh,
    "arccosh": jnp.arccosh,
    "arctanh": jnp.arctanh,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
    "reciprocal": lambda x: 1.0 / x,
    "negative": jnp.negative,
    "logical_not": lambda x: (x == 0).astype(x.dtype),
    "erf": jax.scipy.special.erf,
    "erfinv": jax.scipy.special.erfinv,
    "gamma": lambda x: jnp.exp(jax.scipy.special.gammaln(x)),
    "gammaln": jax.scipy.special.gammaln,
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "softsign": jax.nn.soft_sign,
    "softrelu": jax.nn.softplus,
    "_copy": lambda x: x + 0,
    "identity": lambda x: x,
    "zeros_like": jnp.zeros_like,
    "ones_like": jnp.ones_like,
    "isnan": jnp.isnan,
    "isinf": jnp.isinf,
    "isfinite": jnp.isfinite,
}

for _name, _f in _UNARY.items():
    register_op(_name)(_f)


@register_op("clip")
def _clip(x, a_min=None, a_max=None):
    return jnp.clip(x, a_min, a_max)


@register_op("Cast", aliases=("cast",))
def _cast(x, dtype="float32"):
    from ..base import np_dtype
    return x.astype(np_dtype(dtype))


@register_op("LeakyReLU", input_names=("data", "gamma"))
def _leaky_relu(x, *rest, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334):
    # reference: src/operator/leaky_relu-inl.h (leaky/prelu/elu/selu/gelu,
    # rrelu uses the midpoint of [lower,upper] at inference)
    if act_type == "leaky":
        return jnp.where(x > 0, x, slope * x)
    if act_type == "prelu":
        gamma = rest[0]
        return jnp.where(x > 0, x, gamma * x)
    if act_type == "elu":
        return jnp.where(x > 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        a, s = 1.6732632423543772, 1.0507009873554805
        return s * jnp.where(x > 0, x, a * jnp.expm1(x))
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(x > 0, x, mid * x)
    raise ValueError("unknown LeakyReLU act_type %r" % act_type)


# ---------------------------------------------------------------------------
# binary (elemwise_* requires same shape; broadcast_* broadcasts — the
# reference keeps them separate ops, we keep the names but both broadcast)
# ---------------------------------------------------------------------------

def _logical(fn):
    def wrapped(a, b):
        return fn(a != 0, b != 0).astype(a.dtype)
    return wrapped


_BINARY = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "mod": jnp.mod,
    "power": jnp.power,
    "maximum": jnp.maximum,
    "minimum": jnp.minimum,
    "hypot": jnp.hypot,
    "equal": lambda a, b: (a == b).astype(a.dtype),
    "not_equal": lambda a, b: (a != b).astype(a.dtype),
    "greater": lambda a, b: (a > b).astype(a.dtype),
    "greater_equal": lambda a, b: (a >= b).astype(a.dtype),
    "lesser": lambda a, b: (a < b).astype(a.dtype),
    "lesser_equal": lambda a, b: (a <= b).astype(a.dtype),
    "logical_and": _logical(jnp.logical_and),
    "logical_or": _logical(jnp.logical_or),
    "logical_xor": _logical(jnp.logical_xor),
}

for _name, _f in _BINARY.items():
    register_op("broadcast_" + _name)(_f)

for _name in ("add", "sub", "mul", "div"):
    alias("elemwise_" + _name, "broadcast_" + _name)
alias("_plus", "broadcast_add")
alias("_minus", "broadcast_sub")
alias("_mul", "broadcast_mul")
alias("_div", "broadcast_div")
alias("_mod", "broadcast_mod")
alias("_power", "broadcast_power")
alias("_maximum", "broadcast_maximum")
alias("_minimum", "broadcast_minimum")
alias("_hypot", "broadcast_hypot")
alias("_equal", "broadcast_equal")
alias("_not_equal", "broadcast_not_equal")
alias("_greater", "broadcast_greater")
alias("_greater_equal", "broadcast_greater_equal")
alias("_lesser", "broadcast_lesser")
alias("_lesser_equal", "broadcast_lesser_equal")


# ---------------------------------------------------------------------------
# scalar variants (reference: elemwise_binary_scalar_op files; internal
# _plus_scalar etc. names are what the front ends call)
# ---------------------------------------------------------------------------

_SCALAR = {
    "_plus_scalar": lambda x, scalar=0.0: x + scalar,
    "_minus_scalar": lambda x, scalar=0.0: x - scalar,
    "_rminus_scalar": lambda x, scalar=0.0: scalar - x,
    "_mul_scalar": lambda x, scalar=1.0: x * scalar,
    "_div_scalar": lambda x, scalar=1.0: x / scalar,
    "_rdiv_scalar": lambda x, scalar=1.0: scalar / x,
    "_mod_scalar": lambda x, scalar=1.0: jnp.mod(x, scalar),
    "_rmod_scalar": lambda x, scalar=1.0: jnp.mod(scalar, x),
    "_power_scalar": lambda x, scalar=1.0: jnp.power(x, scalar),
    "_rpower_scalar": lambda x, scalar=1.0: jnp.power(scalar, x),
    "_maximum_scalar": lambda x, scalar=0.0: jnp.maximum(x, scalar),
    "_minimum_scalar": lambda x, scalar=0.0: jnp.minimum(x, scalar),
    "_hypot_scalar": lambda x, scalar=0.0: jnp.hypot(x, scalar),
    "_equal_scalar": lambda x, scalar=0.0: (x == scalar).astype(x.dtype),
    "_not_equal_scalar": lambda x, scalar=0.0: (x != scalar).astype(x.dtype),
    "_greater_scalar": lambda x, scalar=0.0: (x > scalar).astype(x.dtype),
    "_greater_equal_scalar":
        lambda x, scalar=0.0: (x >= scalar).astype(x.dtype),
    "_lesser_scalar": lambda x, scalar=0.0: (x < scalar).astype(x.dtype),
    "_lesser_equal_scalar":
        lambda x, scalar=0.0: (x <= scalar).astype(x.dtype),
    "_logical_and_scalar":
        lambda x, scalar=0.0: ((x != 0) & (scalar != 0)).astype(x.dtype),
    "_logical_or_scalar":
        lambda x, scalar=0.0: ((x != 0) | (scalar != 0)).astype(x.dtype),
    "_logical_xor_scalar":
        lambda x, scalar=0.0: ((x != 0) ^ (scalar != 0)).astype(x.dtype),
    "_scatter_plus_scalar": lambda x, scalar=0.0: x + scalar,
}

for _name, _f in _SCALAR.items():
    register_op(_name)(_f)


@register_op("smooth_l1")
def _smooth_l1(x, scalar=1.0):
    s2 = scalar * scalar
    return jnp.where(jnp.abs(x) < 1.0 / s2,
                     0.5 * s2 * jnp.square(x),
                     jnp.abs(x) - 0.5 / s2)


@register_op("add_n", aliases=("ElementWiseSum", "_sum_nary"))
def _add_n(*args):
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


# -- symbolic metadata -------------------------------------------------------
from .registry import get_op as _get_op

def _leaky_inputs(params):
    if params.get("act_type", "leaky") == "prelu":
        return ("data", "gamma")
    return ("data",)

_get_op("LeakyReLU").active_inputs = _leaky_inputs


# scalar-arith ops take the scalar as a traced arg so varying Python
# scalars in a loop do not trigger one compilation per distinct value
for _name in _SCALAR:
    _get_op(_name).dynamic_params = ("scalar",)
_get_op("smooth_l1").dynamic_params = ("scalar",)
