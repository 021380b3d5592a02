"""Registry-wide op conformance sweep against live TF / torch twins.

VERDICT r3 #4: the TF corpus gate covers importer *rules*; this sweep
exercises the OP REGISTRY's edge semantics directly against the reference
ecosystem (live tensorflow, torch where TF lacks the op, numpy where numpy
IS the ecosystem twin, e.g. FFT). Focus is the edge inputs where silent
divergence hides: empty segments, NaN propagation through min/max, ties in
argmax/topk, banker's rounding, negative operands in integer div/mod,
asymmetric SAME padding, exclusive/reverse cumulations, int dtypes.

The gate test at the bottom counts DISTINCT registry ops exercised here and
fails if the sweep shrinks (ref: SURVEY §4 conformance rows,
`ops/declarable/generic/**` semantics).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.registry import exec_op, names as registry_names

tf = pytest.importorskip("tensorflow")

F32 = np.float32
I32 = np.int32
NAN = np.float32("nan")


def _t(fn, *args, **kw):
    """Run a tf callable and return numpy."""
    r = fn(*args, **kw)
    if isinstance(r, (list, tuple)):
        return [np.asarray(x) for x in r]
    return np.asarray(r)


# Each case: (id, op, args, attrs, twin_fn, kwargs-for-compare)
# twin_fn receives the SAME positional numpy args.
CASES = []


def case(id, op, args, attrs, twin, rtol=1e-5, atol=1e-6, out=0,
         dtype_strict=True):
    CASES.append((id, op, args, attrs, twin, rtol, atol, out, dtype_strict))


rng = np.random.default_rng(0)
x34 = rng.normal(size=(3, 4)).astype(F32)
xpos = (np.abs(x34) + 0.1).astype(F32)
xunit = np.clip(x34 * 0.3, -0.95, 0.95).astype(F32)
xn = np.array([1.0, NAN, -2.0, NAN, 3.0], F32)
yn = np.array([NAN, 2.0, -3.0, 1.0, NAN], F32)
ints = np.array([-7, -3, -1, 1, 3, 7], I32)
intd = np.array([2, -2, 3, -3, 2, -2], I32)

# ---- unary elementwise (NaN must propagate; dtype preserved) -------------
for nm, twin in [
    ("abs", tf.abs), ("neg", lambda x: -x), ("exp", tf.exp),
    ("log", tf.math.log), ("log1p", tf.math.log1p),
    ("expm1", tf.math.expm1), ("sqrt", tf.sqrt), ("rsqrt", tf.math.rsqrt),
    ("square", tf.square), ("reciprocal", tf.math.reciprocal),
    ("sign", tf.sign), ("floor", tf.floor), ("ceil", tf.math.ceil),
    ("sigmoid", tf.sigmoid), ("tanh", tf.tanh),
    ("softplus", tf.math.softplus), ("softsign", tf.math.softsign),
    ("erf", tf.math.erf), ("erfc", tf.math.erfc),
    ("lgamma", tf.math.lgamma), ("digamma", tf.math.digamma),
    ("sin", tf.sin), ("cos", tf.cos), ("tan", tf.tan),
    ("sinh", tf.sinh), ("cosh", tf.cosh),
    ("log_sigmoid", tf.math.log_sigmoid),
    ("bessel... skip", None),
]:
    if twin is None:
        continue
    case(f"{nm}_pos", nm, (xpos,), {}, lambda x, t=twin: _t(t, x))
for nm, twin in [("asin", tf.asin), ("acos", tf.acos), ("atan", tf.atan),
                 ("atanh", tf.atanh), ("asinh", tf.asinh)]:
    case(f"{nm}_unit", nm, (xunit,), {}, lambda x, t=twin: _t(t, x))
case("acosh", "acosh", ((np.abs(x34) + 1.1).astype(F32),), {},
     lambda x: _t(tf.acosh, x))
case("exp_nan", "exp", (xn,), {}, lambda x: _t(tf.exp, x))
case("tanh_nan", "tanh", (xn,), {}, lambda x: _t(tf.tanh, x))
case("rint_ties_to_even", "rint",
     (np.array([0.5, 1.5, 2.5, -0.5, -1.5, 3.5], F32),), {},
     lambda x: _t(tf.math.rint, x))
case("round_ties_to_even", "round",
     (np.array([0.5, 1.5, 2.5, -0.5, -1.5, 3.5], F32),), {},
     lambda x: _t(tf.round, x))
case("trunc", "trunc", (np.array([1.7, -1.7, 0.3, -0.3], F32),), {},
     lambda x: np.trunc(x))
case("relu", "relu", (xn,), {}, lambda x: _t(tf.nn.relu, x))
case("relu6", "relu6", (np.array([-1., 3., 7., 6.], F32),), {},
     lambda x: _t(tf.nn.relu6, x))
case("elu", "elu", (x34,), {}, lambda x: _t(tf.nn.elu, x))
case("selu", "selu", (x34,), {}, lambda x: _t(tf.nn.selu, x))
case("gelu", "gelu", (x34,), {},
     lambda x: _t(tf.nn.gelu, x, approximate=True), rtol=1e-4, atol=1e-5)
case("swish", "swish", (x34,), {}, lambda x: _t(tf.nn.silu, x))
case("leakyrelu", "leakyrelu", (x34,), {"alpha": 0.2},
     lambda x: _t(tf.nn.leaky_relu, x, alpha=0.2))
# hard_sigmoid: the DL4J/Keras-2/ONNX-default definition clip(0.2x+0.5)
# — pinned against an explicit twin because tf.keras.activations moved to
# the slope-1/6 variant in Keras 3 (h5 artifacts are the legacy format,
# whose layers mean the 0.2 slope)
case("hard_sigmoid_ref_slope", "hard_sigmoid",
     (np.array([-4., -1., 0., 1., 4.], F32),), {},
     lambda x: np.clip(0.2 * x + 0.5, 0.0, 1.0).astype(F32))

# ---- binary + int/negative edge semantics --------------------------------
case("add", "add", (x34, x34[0]), {}, lambda a, b: _t(tf.add, a, b))
case("sub", "sub", (x34, x34[0]), {}, lambda a, b: _t(tf.subtract, a, b))
case("mul", "mul", (x34, x34[0]), {}, lambda a, b: _t(tf.multiply, a, b))
case("div_f32", "div", (x34, xpos),
     {}, lambda a, b: _t(tf.divide, a, b))
case("realdiv", "realdiv", (x34, xpos), {},
     lambda a, b: _t(tf.realdiv, a, b))
case("floordiv_neg_int", "floordiv", (ints, intd), {},
     lambda a, b: _t(tf.math.floordiv, a, b))
case("floormod_neg_int", "floormod", (ints, intd), {},
     lambda a, b: _t(tf.math.floormod, a, b))
case("mod_neg_int", "mod", (ints, intd), {},
     lambda a, b: _t(tf.math.mod, a, b))
case("truncatediv_neg_int", "truncatediv", (ints, intd), {},
     lambda a, b: _t(tf.truncatediv, a, b))
case("truncatemod_neg_int", "truncatemod", (ints, intd), {},
     lambda a, b: _t(tf.truncatemod, a, b))
case("pow", "pow", (xpos, x34), {}, lambda a, b: _t(tf.pow, a, b),
     rtol=1e-4)
case("maximum_nan", "maximum", (xn, yn), {},
     lambda a, b: _t(tf.maximum, a, b))
case("minimum_nan", "minimum", (xn, yn), {},
     lambda a, b: _t(tf.minimum, a, b))
case("squaredsubtract", "squaredsubtract", (x34, x34[0]), {},
     lambda a, b: _t(tf.math.squared_difference, a, b))
case("atan2", "atan2", (x34, x34[0] + 0.01), {},
     lambda a, b: _t(tf.atan2, a, b))
case("divide_no_nan", "divide_no_nan",
     (x34, np.array([1., 0., 2., 0.], F32)), {},
     lambda a, b: _t(tf.math.divide_no_nan, a, b))
case("igamma", "igamma", (xpos, xpos.T.reshape(3, 4) + 0.2), {},
     lambda a, b: _t(tf.math.igamma, a, b), rtol=1e-4)
case("igammac", "igammac", (xpos, xpos.T.reshape(3, 4) + 0.2), {},
     lambda a, b: _t(tf.math.igammac, a, b), rtol=1e-4)
case("zeta", "zeta", (xpos + 1.5, xpos), {},
     lambda a, b: _t(tf.math.zeta, a, b), rtol=1e-4)
case("polygamma", "polygamma",
     (np.array([1., 2., 3.], F32), np.array([0.5, 1.5, 2.5], F32)), {},
     lambda a, b: _t(tf.math.polygamma, a, b), rtol=1e-4)
case("betainc", "betainc",
     (xpos[0], xpos[1], np.clip(xpos[2], 0.05, 0.95)), {},
     lambda a, b, x: _t(tf.math.betainc, a, b, x), rtol=1e-4)
case("xlogy... skip", "hypot",
     (np.array([3., -5.], F32), np.array([4., 12.], F32)), {},
     lambda a, b: np.hypot(a, b))

# ---- comparisons / logical (NaN compares false; != compares true) --------
case("less_nan", "less", (xn, yn), {}, lambda a, b: _t(tf.less, a, b))
case("less_equal_nan", "less_equal", (xn, yn), {},
     lambda a, b: _t(tf.less_equal, a, b))
case("greater_nan", "greater", (xn, yn), {},
     lambda a, b: _t(tf.greater, a, b))
case("greater_equal_nan", "greater_equal", (xn, yn), {},
     lambda a, b: _t(tf.greater_equal, a, b))
case("equals_nan", "equals", (xn, xn), {}, lambda a, b: _t(tf.equal, a, b))
case("not_equals_nan", "not_equals", (xn, xn), {},
     lambda a, b: _t(tf.not_equal, a, b))
bools = np.array([True, True, False, False])
bools2 = np.array([True, False, True, False])
case("boolean_and", "boolean_and", (bools, bools2), {},
     lambda a, b: _t(tf.logical_and, a, b))
case("boolean_or", "boolean_or", (bools, bools2), {},
     lambda a, b: _t(tf.logical_or, a, b))
case("boolean_xor", "boolean_xor", (bools, bools2), {},
     lambda a, b: _t(tf.math.logical_xor, a, b))
case("boolean_not", "boolean_not", (bools,), {},
     lambda a: _t(tf.logical_not, a))
case("isclose", "isclose", (xn, yn), {},
     lambda a, b: np.isclose(a, b), dtype_strict=False)
case("isnan", "isnan", (xn,), {}, lambda x: _t(tf.math.is_nan, x))
case("isinf", "isinf", (np.array([1., np.inf, -np.inf, NAN], F32),), {},
     lambda x: _t(tf.math.is_inf, x))
case("isfinite", "isfinite", (np.array([1., np.inf, -np.inf, NAN], F32),),
     {}, lambda x: _t(tf.math.is_finite, x))

# ---- bitwise -------------------------------------------------------------
ia = np.array([0b1100, 0b1010, -5, 255], I32)
ib = np.array([0b1010, 0b0110, 3, 7], I32)
case("bitwise_and", "bitwise_and", (ia, ib), {},
     lambda a, b: _t(tf.bitwise.bitwise_and, a, b))
case("bitwise_or", "bitwise_or", (ia, ib), {},
     lambda a, b: _t(tf.bitwise.bitwise_or, a, b))
case("bitwise_xor", "bitwise_xor", (ia, ib), {},
     lambda a, b: _t(tf.bitwise.bitwise_xor, a, b))
case("rshift_bits_neg", "rshift_bits", (ia, ib % 8), {},
     lambda a, b: _t(tf.bitwise.right_shift, a, b))
case("shift_bits", "shift_bits", (ia, ib % 8), {},
     lambda a, b: _t(tf.bitwise.left_shift, a, b))
case("invert_permutation", "invert_permutation",
     (np.array([3, 0, 2, 1], I32),), {},
     lambda p: _t(tf.math.invert_permutation, p))

# ---- reductions ----------------------------------------------------------
xr = rng.normal(size=(2, 3, 4)).astype(F32)
case("reduce_sum_axis", "reduce_sum", (xr,), {"axis": 1},
     lambda x: _t(tf.reduce_sum, x, axis=1), rtol=1e-5)
case("reduce_sum_keepdims", "reduce_sum", (xr,),
     {"axis": (0, 2), "keepdims": True},
     lambda x: _t(tf.reduce_sum, x, axis=(0, 2), keepdims=True))
case("reduce_mean", "reduce_mean", (xr,), {"axis": -1},
     lambda x: _t(tf.reduce_mean, x, axis=-1))
case("reduce_max_nan", "reduce_max", (xn,), {},
     lambda x: _t(tf.reduce_max, x), dtype_strict=False)
case("reduce_min_nan", "reduce_min", (xn,), {},
     lambda x: _t(tf.reduce_min, x), dtype_strict=False)
case("reduce_prod", "reduce_prod", (xr,), {"axis": 2},
     lambda x: _t(tf.reduce_prod, x, axis=2))
case("reduce_any", "reduce_any", (bools.reshape(2, 2),), {"axis": 1},
     lambda x: _t(tf.reduce_any, x, axis=1))
case("reduce_all", "reduce_all", (bools.reshape(2, 2),), {"axis": 1},
     lambda x: _t(tf.reduce_all, x, axis=1))
case("reduce_logsumexp", "reduce_logsumexp", (xr,), {"axis": 1},
     lambda x: _t(tf.reduce_logsumexp, x, axis=1), rtol=1e-5)
case("count_nonzero", "count_nonzero",
     (np.array([[0., 1., 2.], [0., 0., 3.]], F32),), {},
     lambda x: _t(tf.math.count_nonzero, x), dtype_strict=False)
case("argmax_ties_first", "argmax",
     (np.array([[1., 7., 7., 2.], [5., 5., 1., 5.]], F32),), {"axis": 1},
     lambda x: _t(tf.argmax, x, axis=1), dtype_strict=False)
case("argmin_ties_first", "argmin",
     (np.array([[1., 1., 7., 2.], [5., 0., 0., 5.]], F32),), {"axis": 1},
     lambda x: _t(tf.argmin, x, axis=1), dtype_strict=False)
case("cumsum_excl_rev", "cumsum", (x34,),
     {"axis": 1, "exclusive": True, "reverse": True},
     lambda x: _t(tf.cumsum, x, axis=1, exclusive=True, reverse=True))
case("cumprod_excl", "cumprod", (x34,), {"axis": 0, "exclusive": True},
     lambda x: _t(tf.math.cumprod, x, axis=0, exclusive=True))
case("moments", "moments", (xr,), {"axes": (0, 1)},
     lambda x: _t(lambda y: tf.nn.moments(y, axes=[0, 1]), x), out=(0, 1))
case("l2_loss", "l2_loss", (x34,), {}, lambda x: _t(tf.nn.l2_loss, x))
case("zero_fraction", "zero_fraction",
     (np.array([0., 1., 0., 3.], F32),), {},
     lambda x: _t(tf.math.zero_fraction, x))

# ---- segments (EMPTY SEGMENT FILL is the r3-found divergence) ------------
seg_d = np.array([1., 2., 3., -4.], F32)
seg_i = np.array([0, 0, 2, 2])
seg_int = np.array([5, -2, 7, 1], I32)
case("unsorted_segment_max_empty", "unsorted_segment_max",
     (seg_d, seg_i), {"num_segments": 4},
     lambda d, i: _t(tf.math.unsorted_segment_max, d, i, 4))
case("unsorted_segment_min_empty", "unsorted_segment_min",
     (seg_d, seg_i), {"num_segments": 4},
     lambda d, i: _t(tf.math.unsorted_segment_min, d, i, 4))
case("unsorted_segment_max_int_empty", "unsorted_segment_max",
     (seg_int, seg_i), {"num_segments": 4},
     lambda d, i: _t(tf.math.unsorted_segment_max, d, i, 4))
case("unsorted_segment_sum_empty", "unsorted_segment_sum",
     (seg_d, seg_i), {"num_segments": 4},
     lambda d, i: _t(tf.math.unsorted_segment_sum, d, i, 4))
case("unsorted_segment_prod_empty", "unsorted_segment_prod",
     (seg_d, seg_i), {"num_segments": 4},
     lambda d, i: _t(tf.math.unsorted_segment_prod, d, i, 4))
case("unsorted_segment_mean_empty", "unsorted_segment_mean",
     (seg_d, seg_i), {"num_segments": 4},
     lambda d, i: _t(tf.math.unsorted_segment_mean, d, i, 4))
case("unsorted_segment_sqrt_n", "unsorted_segment_sqrt_n",
     (seg_d, seg_i), {"num_segments": 4},
     lambda d, i: _t(tf.math.unsorted_segment_sqrt_n, d, i, 4))
case("segment_sum_gap", "segment_sum",
     (seg_d, np.array([0, 0, 3, 3])), {},
     lambda d, i: _t(tf.math.segment_sum, d, i))
case("segment_mean_gap", "segment_mean",
     (seg_d, np.array([0, 0, 3, 3])), {},
     lambda d, i: _t(tf.math.segment_mean, d, i))
case("bincount", "bincount", (np.array([1, 1, 3, 0, 3, 3], I32),), {},
     lambda x: _t(tf.math.bincount, x), dtype_strict=False)

# ---- padding (asymmetric; reflect vs symmetric) --------------------------
case("pad_const_asym", "pad", (x34,), {"paddings": ((1, 2), (0, 3)),
                                       "constant_values": 2.5},
     lambda x: _t(tf.pad, x, [[1, 2], [0, 3]], constant_values=2.5))
case("pad_reflect_asym", "pad", (x34,),
     {"paddings": ((1, 2), (2, 0)), "mode": "REFLECT"},
     lambda x: _t(tf.pad, x, [[1, 2], [2, 0]], mode="REFLECT"))
case("pad_symmetric_asym", "pad", (x34,),
     {"paddings": ((2, 1), (0, 2)), "mode": "SYMMETRIC"},
     lambda x: _t(tf.pad, x, [[2, 1], [0, 2]], mode="SYMMETRIC"))
case("mirror_pad_reflect", "mirror_pad", (x34,),
     {"paddings": [[1, 1], [2, 1]], "mode": "REFLECT"},
     lambda x: _t(tf.pad, x, [[1, 1], [2, 1]], mode="REFLECT"))

# ---- shape / gather / scatter -------------------------------------------
case("concat", "concat", (x34, x34), {"axis": 1},
     lambda a, b: _t(tf.concat, [a, b], axis=1))
case("stack_neg_axis", "stack", (x34, x34), {"axis": -1},
     lambda a, b: _t(tf.stack, [a, b], axis=-1))
case("tile", "tile", (x34,), {"reps": (2, 3)},
     lambda x: _t(tf.tile, x, [2, 3]))
case("reverse", "reverse", (xr,), {"axis": (0, 2)},
     lambda x: _t(tf.reverse, x, axis=[0, 2]))
case("transpose_perm", "transpose", (xr,), {"perm": (2, 0, 1)},
     lambda x: _t(tf.transpose, x, perm=[2, 0, 1]))
case("expand_dims", "expand_dims", (x34,), {"axis": 1},
     lambda x: _t(tf.expand_dims, x, axis=1))
case("squeeze_axis", "squeeze", (x34.reshape(3, 1, 4, 1),), {"axis": 1},
     lambda x: _t(tf.squeeze, x, axis=1))
case("reshape_minus1", "reshape", (xr,), {"shape": (2, -1)},
     lambda x: _t(tf.reshape, x, (2, -1)))
case("gather_axis", "gather", (xr, np.array([2, 0, 2])), {"axis": 2},
     lambda x, i: _t(tf.gather, x, i, axis=2))
case("gather_nd", "gather_nd", (xr, np.array([[0, 1], [1, 2]])), {},
     lambda x, i: _t(tf.gather_nd, x, i))
case("scatter_nd_dup_adds", "scatter_nd",
     (np.array([[1], [1], [3]]), np.array([9., 10., 11.], F32)),
     {"shape": (6,)},
     lambda i, u: _t(tf.scatter_nd, i, u, [6]))
case("one_hot_on_off", "one_hot", (np.array([0, 2, 1, 3]),),
     {"depth": 4, "on_value": 5.0, "off_value": -1.0},
     lambda i: _t(tf.one_hot, i, 4, on_value=5.0, off_value=-1.0))
case("one_hot_axis0", "one_hot", (np.array([0, 2, 1]),),
     {"depth": 3, "axis": 0}, lambda i: _t(tf.one_hot, i, 3, axis=0))
case("roll", "roll", (x34,), {"shift": (1, -2), "axis": (0, 1)},
     lambda x: _t(tf.roll, x, [1, -2], [0, 1]))
case("rot90", "rot90", (x34,), {"k": 3},
     lambda x: np.rot90(x, k=3))
case("slice", "slice", (xr,), {"begin": (0, 1, 1), "size": (2, 2, 3)},
     lambda x: _t(tf.slice, x, [0, 1, 1], [2, 2, 3]))
case("strided_slice_neg_stride", "strided_slice", (x34,),
     {"begin": (2, 3), "end": (0, 0), "strides": (-1, -2)},
     lambda x: x[2:0:-1, 3:0:-2])
case("broadcast_to", "broadcast_to", (x34[0],), {"shape": (5, 3, 4)},
     lambda x: _t(tf.broadcast_to, x, [5, 3, 4]))
case("where_select_nan", "where", (bools[:4].reshape(2, 2),
                                   xn[:4].reshape(2, 2),
                                   yn[:4].reshape(2, 2)), {},
     lambda c, a, b: _t(tf.where, c, a, b))
case("where_coords", "where", (np.array([[True, False], [False, True]]),),
     {}, lambda c: _t(tf.where, c), dtype_strict=False)
case("reverse_sequence", "reverse_sequence",
     (xr, np.array([2, 3], I32)), {"seq_axis": 1, "batch_axis": 0},
     lambda x, sl: _t(tf.reverse_sequence, x, sl, seq_axis=1,
                      batch_axis=0))
case("sequence_mask", "sequence_mask", (np.array([1, 0, 3], I32),),
     {"maxlen": 4}, lambda l: _t(tf.sequence_mask, l, 4))
case("unique", "unique", (np.array([1, 1, 2, 4, 4, 4, 7, 8, 8], I32),),
     {}, lambda x: _t(tf.unique, x), out=(0, 1), dtype_strict=False)
case("unique_with_counts", "unique_with_counts",
     (np.array([1, 1, 2, 4, 4, 4, 7, 8, 8], I32),), {},
     lambda x: _t(tf.unique_with_counts, x), out=(0, 1, 2),
     dtype_strict=False)
case("listdiff", "listdiff",
     (np.array([1, 2, 3, 4, 5, 6], I32), np.array([1, 3, 5], I32)), {},
     lambda a, b: _t(tf.sets.difference if False else
                     lambda x, y: tf.raw_ops.ListDiff(x=x, y=y), a, b),
     out=(0, 1), dtype_strict=False)
case("dynamic_partition", "dynamic_partition",
     (np.array([10., 20., 30., 40.], F32), np.array([1, 0, 1, 0], I32),
      2), {},
     lambda d, p, n: _t(tf.dynamic_partition, d, p, n), out=(0, 1))
case("searchsorted", "searchsorted",
     (np.array([1., 3., 5., 7.], F32), np.array([0., 4., 8., 5.], F32)),
     {}, lambda s, v: _t(tf.searchsorted, s, v), dtype_strict=False)
case("histogram_fixed_width", "histogram_fixed_width",
     (np.array([-1., 0., 1.5, 2., 5., 15.], F32),),
     {"value_range": (0.0, 10.0), "nbins": 5},
     lambda v: _t(tf.histogram_fixed_width, v, [0.0, 10.0], nbins=5),
     dtype_strict=False)
case("meshgrid", "meshgrid",
     (np.array([1., 2., 3.], F32), np.array([4., 5.], F32)), {},
     lambda a, b: _t(tf.meshgrid, a, b), out=(0, 1))
case("eye", "eye", (), {"n": 3, "m": 5},
     lambda: np.eye(3, 5, dtype=F32))
case("fill", "fill", (), {"shape": (2, 3), "value": 7.5},
     lambda: np.full((2, 3), 7.5, F32))
case("range", "range", (), {"start": 2, "limit": 11, "delta": 3},
     lambda: np.arange(2, 11, 3), dtype_strict=False)
case("linspace", "linspace", (), {"start": 0.0, "stop": 1.0, "num": 5},
     lambda: np.linspace(0.0, 1.0, 5, dtype=F32))
case("diag", "diag", (np.array([1., 2., 3.], F32),), {},
     lambda x: _t(tf.linalg.diag, x))
case("diag_part", "diag_part", (x34[:3, :3],), {},
     lambda x: _t(tf.linalg.diag_part, x))
case("matrix_band_part", "matrix_band_part", (x34,),
     {"lower": 1, "upper": 0},
     lambda x: _t(tf.linalg.band_part, x, 1, 0))
case("tril", "tril", (x34,), {}, lambda x: np.tril(x))
case("triu", "triu", (x34,), {}, lambda x: np.triu(x))
case("trace", "trace", (x34[:3, :3],), {},
     lambda x: _t(tf.linalg.trace, x))
case("top_k", "top_k", (np.array([[1., 9., 3., 9.], [4., 2., 8., 1.]],
                                 F32),), {"k": 2},
     lambda x: _t(lambda y: tf.math.top_k(y, k=2), x), out=(0, 1),
     dtype_strict=False)
case("in_top_k", "in_top_k",
     (np.array([[0.1, 0.9, 0.0], [0.9, 0.1, 0.0]], F32),
      np.array([1, 2], I32)), {"k": 1},
     lambda p, t: _t(tf.math.in_top_k, t, p, 1))
case("nth_element", "nth_element",
     (np.array([[3., 1., 4., 1.], [5., 9., 2., 6.]], F32),), {"n": 2},
     lambda x: _t(lambda y: tf.raw_ops.NthElement(input=y, n=2), x))

# ---- softmax & losses ----------------------------------------------------
case("softmax_axis", "softmax", (xr,), {"axis": 1},
     lambda x: _t(tf.nn.softmax, x, axis=1))
case("log_softmax", "log_softmax", (x34,), {},
     lambda x: _t(tf.nn.log_softmax, x))
case("softmax_xent_logits", "softmax_cross_entropy_with_logits",
     (x34, np.eye(4, dtype=F32)[[0, 2, 1]]), {},
     lambda z, l: _t(tf.nn.softmax_cross_entropy_with_logits,
                     labels=l, logits=z))
case("sigmoid_xent", "sigmoid_cross_entropy",
     (x34, np.eye(4, dtype=F32)[[0, 2, 1]]), {},
     lambda z, l: _t(tf.nn.sigmoid_cross_entropy_with_logits,
                     labels=l, logits=z))
case("weighted_xent", "weighted_cross_entropy_with_logits",
     (np.eye(4, dtype=F32)[[0, 2, 1]], x34), {"pos_weight": 2.0},
     lambda l, z: _t(tf.nn.weighted_cross_entropy_with_logits,
                     labels=l, logits=z, pos_weight=2.0))
case("l2_normalize", "l2_normalize", (x34,), {"axis": 1},
     lambda x: _t(tf.math.l2_normalize, x, axis=1))
case("lrn", "lrn", (rng.normal(size=(1, 4, 4, 8)).astype(F32),),
     {"depth_radius": 2, "bias": 1.0, "alpha": 1e-3, "beta": 0.75},
     lambda x: _t(tf.nn.local_response_normalization, x, depth_radius=2,
                  bias=1.0, alpha=1e-3, beta=0.75), rtol=1e-4)
case("bias_add", "bias_add", (x34, np.array([1., 2., 3., 4.], F32)), {},
     lambda x, b: _t(tf.nn.bias_add, x, b))

# ---- conv / pool SAME-padding semantics ----------------------------------
img = rng.normal(size=(1, 7, 7, 3)).astype(F32)
ker = rng.normal(size=(3, 3, 3, 5)).astype(F32) * 0.3
case("conv2d_same_s2", "conv2d", (img, ker),
     {"strides": (2, 2), "padding": "SAME"},
     lambda x, k: _t(tf.nn.conv2d, x, k, [1, 2, 2, 1], "SAME"), rtol=1e-4,
     atol=1e-5)
case("conv2d_valid", "conv2d", (img, ker),
     {"strides": (1, 1), "padding": "VALID"},
     lambda x, k: _t(tf.nn.conv2d, x, k, [1, 1, 1, 1], "VALID"), rtol=1e-4,
     atol=1e-5)
dker = rng.normal(size=(3, 3, 3, 2)).astype(F32) * 0.3
case("depthwise_conv2d_same", "depthwise_conv2d", (img, dker),
     {"strides": (1, 1), "padding": "SAME"},
     lambda x, k: _t(tf.nn.depthwise_conv2d, x, k, [1, 1, 1, 1], "SAME"),
     rtol=1e-4, atol=1e-5)
case("maxpool2d_same_s2", "maxpool2d", (img,),
     {"kernel": (3, 3), "strides": (2, 2), "padding": "SAME"},
     lambda x: _t(tf.nn.max_pool2d, x, 3, 2, "SAME"))
case("avgpool2d_same_excludes_pad", "avgpool2d", (img,),
     {"kernel": (3, 3), "strides": (2, 2), "padding": "SAME"},
     lambda x: _t(tf.nn.avg_pool2d, x, 3, 2, "SAME"), rtol=1e-5)
case("space_to_depth", "space_to_depth",
     (rng.normal(size=(1, 4, 6, 3)).astype(F32),), {"block_size": 2},
     lambda x: _t(tf.nn.space_to_depth, x, 2))
case("depth_to_space", "depth_to_space",
     (rng.normal(size=(1, 2, 3, 12)).astype(F32),), {"block_size": 2},
     lambda x: _t(tf.nn.depth_to_space, x, 2))
case("extract_image_patches", "extract_image_patches", (img,),
     {"ksizes": (3, 3), "strides": (2, 2), "rates": (1, 1),
      "padding": "VALID"},
     lambda x: _t(tf.image.extract_patches, x, [1, 3, 3, 1], [1, 2, 2, 1],
                  [1, 1, 1, 1], "VALID"))

# ---- image ---------------------------------------------------------------
imr = np.clip(rng.normal(size=(1, 4, 4, 3)).astype(F32) * 0.3 + 0.5, 0, 1)
case("resize_bilinear_up", "resize_bilinear", (imr,), {"size": (7, 9)},
     lambda x: _t(tf.image.resize, x, [7, 9], method="bilinear"),
     rtol=1e-4, atol=1e-5)
case("resize_nearest", "resize_nearest_neighbor", (imr,), {"size": (9, 7)},
     lambda x: _t(tf.image.resize, x, [9, 7], method="nearest"))
# DOWNSCALE is the divergence hotspot (kernel-footprint choices differ
# across libraries); all three methods match TF tightly — bicubic via the
# exact keyscubic weight-matrix reconstruction (A=-0.5, drop+renormalize
# boundary taps, 1024-entry table quantization) in ops/extended.py
case("resize_bilinear_down", "resize_bilinear",
     (rng.normal(size=(1, 8, 8, 3)).astype(F32),), {"size": (3, 5)},
     lambda x: _t(tf.image.resize, x, [3, 5], method="bilinear"),
     rtol=1e-4, atol=1e-5)
case("resize_nearest_down", "resize_nearest_neighbor",
     (rng.normal(size=(1, 8, 8, 3)).astype(F32),), {"size": (3, 5)},
     lambda x: _t(tf.image.resize, x, [3, 5], method="nearest"))
case("resize_bicubic_down", "resize_bicubic",
     (rng.normal(size=(1, 8, 8, 3)).astype(F32),), {"size": (3, 5)},
     lambda x: _t(tf.image.resize, x, [3, 5], method="bicubic"),
     rtol=1e-4, atol=1e-5)
case("resize_bicubic_up", "resize_bicubic",
     (rng.normal(size=(1, 4, 6, 3)).astype(F32),), {"size": (9, 11)},
     lambda x: _t(tf.image.resize, x, [9, 11], method="bicubic"),
     rtol=1e-4, atol=1e-5)
case("rgb_to_hsv", "rgb_to_hsv", (imr,), {},
     lambda x: _t(tf.image.rgb_to_hsv, x), rtol=1e-4, atol=1e-5)
case("hsv_to_rgb", "hsv_to_rgb",
     (np.clip(rng.random((1, 4, 4, 3)).astype(F32), 0.01, 0.99),), {},
     lambda x: _t(tf.image.hsv_to_rgb, x), rtol=1e-4, atol=1e-5)
case("rgb_to_grayscale", "rgb_to_grayscale", (imr,), {},
     lambda x: _t(tf.image.rgb_to_grayscale, x), rtol=1e-4, atol=1e-5)
case("rgb_to_yiq", "rgb_to_yiq", (imr,), {},
     lambda x: _t(tf.image.rgb_to_yiq, x), rtol=1e-3, atol=5e-5)
case("rgb_to_yuv", "rgb_to_yuv", (imr,), {},
     lambda x: _t(tf.image.rgb_to_yuv, x), rtol=1e-4, atol=1e-5)
case("adjust_contrast", "adjust_contrast", (imr,), {"factor": 1.7},
     lambda x: _t(tf.image.adjust_contrast, x, 1.7), rtol=1e-4, atol=1e-5)
case("adjust_saturation", "adjust_saturation", (imr,), {"factor": 0.6},
     lambda x: _t(tf.image.adjust_saturation, x, 0.6), rtol=1e-4,
     atol=1e-5)
case("adjust_hue", "adjust_hue", (imr,), {"delta": 0.15},
     lambda x: _t(tf.image.adjust_hue, x, 0.15), rtol=1e-3, atol=1e-4)

# ---- linalg --------------------------------------------------------------
spd = (x34[:3, :3] @ x34[:3, :3].T + 3 * np.eye(3, dtype=F32)).astype(F32)
sq = (x34[:3, :3] + 2 * np.eye(3, dtype=F32)).astype(F32)
case("matmul", "matmul", (x34, x34.T.copy()), {},
     lambda a, b: _t(tf.matmul, a, b), rtol=1e-4, atol=1e-5)
case("matmul_transpose_b", "matmul", (x34, x34), {"transpose_b": True},
     lambda a, b: _t(tf.matmul, a, b, transpose_b=True), rtol=1e-4,
     atol=1e-5)
case("cholesky", "cholesky", (spd,), {},
     lambda x: _t(tf.linalg.cholesky, x), rtol=1e-3, atol=1e-4)
case("matrix_determinant", "matrix_determinant", (sq,), {},
     lambda x: _t(tf.linalg.det, x), rtol=1e-3)
case("matrix_inverse", "matrix_inverse", (sq,), {},
     lambda x: _t(tf.linalg.inv, x), rtol=1e-3, atol=1e-4)
case("solve", "solve", (spd, x34[:3, :2].copy()), {},
     lambda a, b: _t(tf.linalg.solve, a, b), rtol=1e-3, atol=1e-4)
case("triangular_solve", "triangular_solve",
     (np.tril(spd).astype(F32), x34[:3, :2].copy()),
     {"lower": True},
     lambda a, b: _t(tf.linalg.triangular_solve, a, b, lower=True),
     rtol=1e-3, atol=1e-4)
case("cross", "cross",
     (np.array([[1., 0., 0.], [0., 2., 0.]], F32),
      np.array([[0., 1., 0.], [0., 0., 3.]], F32)), {},
     lambda a, b: _t(tf.linalg.cross, a, b))
case("tensordot", "tensordot", (xr, xr.transpose(1, 2, 0).copy()),
     {"axes": 2}, lambda a, b: np.tensordot(a, b, axes=2), rtol=1e-4,
     atol=1e-4)
case("einsum", "einsum", (x34, x34.T.copy()), {"equation": "ij,jk->ik"},
     lambda a, b: np.einsum("ij,jk->ik", a, b), rtol=1e-4, atol=1e-5)
case("kron", "kron", (x34[:2, :2], x34[1:3, 1:3]), {},
     lambda a, b: np.kron(a, b), rtol=1e-5)
case("matrix_set_diag", "matrix_set_diag",
     (x34[:3, :3], np.array([9., 8., 7.], F32)), {},
     lambda m, d: _t(tf.linalg.set_diag, m, d))
case("matrix_diag", "matrix_diag", (np.array([1., 2., 3.], F32),), {},
     lambda d: _t(tf.linalg.diag, d))

# ---- fft (numpy is the ecosystem twin) -----------------------------------
cx = rng.normal(size=(8,)).astype(F32)
case("fft", "fft", (cx.astype(np.complex64),), {},
     lambda x: np.fft.fft(x).astype(np.complex64), rtol=1e-4, atol=1e-4)
case("ifft", "ifft", (cx.astype(np.complex64),), {},
     lambda x: np.fft.ifft(x).astype(np.complex64), rtol=1e-4, atol=1e-4)
case("rfft", "rfft", (cx,), {},
     lambda x: np.fft.rfft(x).astype(np.complex64), rtol=1e-4, atol=1e-4)
case("irfft", "irfft", (np.fft.rfft(cx).astype(np.complex64),), {},
     lambda x: np.fft.irfft(x).astype(F32), rtol=1e-4, atol=1e-4)
case("fft2", "fft2", (rng.normal(size=(4, 4)).astype(F32)
                      .astype(np.complex64),), {},
     lambda x: np.fft.fft2(x).astype(np.complex64), rtol=1e-4, atol=1e-3)

# ---- clipping / misc -----------------------------------------------------
case("clipbyvalue_nan", "clipbyvalue", (xn,),
     {"clip_value_min": -1.0, "clip_value_max": 1.0},
     lambda x: _t(tf.clip_by_value, x, -1.0, 1.0))
case("clipbynorm", "clipbynorm", (x34,), {"clipnorm": 1.5},
     lambda x: _t(tf.clip_by_norm, x, 1.5), rtol=1e-5)
case("cast_f_to_i_truncates", "cast",
     (np.array([1.7, -1.7, 2.5, -2.5], F32),), {"dtype": "int32"},
     lambda x: _t(tf.cast, x, tf.int32))
case("floor_int_passthrough", "to_int32",
     (np.array([1.9, -1.9], F32),), {},
     lambda x: x.astype(I32))




# ---- round-4 tranche 2: scatter / morphology / image-box / ctc ----------
def _torch():
    import torch
    return torch


case("scatter_update", "scatter_update",
     (np.zeros((5, 2), F32), np.array([3, 1]),
      np.array([[1., 2.], [3., 4.]], F32)), {},
     lambda r, i, u: _t(lambda a, b, c: tf.tensor_scatter_nd_update(
         a, b[:, None], c), r, i, u))
case("scatter_add_dup", "scatter_add",
     (np.zeros((4,), F32), np.array([1, 1, 2]),
      np.array([5., 6., 7.], F32)), {},
     lambda r, i, u: _t(lambda a, b, c: tf.tensor_scatter_nd_add(
         a, b[:, None], c), r, i, u))
case("scatter_max", "scatter_max",
     (np.ones((4,), F32), np.array([0, 0, 3]),
      np.array([5., 2., -1.], F32)), {},
     lambda r, i, u: _t(lambda a, b, c: tf.tensor_scatter_nd_max(
         a, b[:, None], c), r, i, u))
case("scatter_min", "scatter_min",
     (np.ones((4,), F32), np.array([0, 0, 3]),
      np.array([5., -2., 0.5], F32)), {},
     lambda r, i, u: _t(lambda a, b, c: tf.tensor_scatter_nd_min(
         a, b[:, None], c), r, i, u))
case("scatter_sub", "scatter_sub",
     (np.full((4,), 10.0, F32), np.array([2, 2]),
      np.array([3., 4.], F32)), {},
     lambda r, i, u: _t(lambda a, b, c: tf.tensor_scatter_nd_sub(
         a, b[:, None], c), r, i, u))
case("gather_elements", "gather_elements",
     (x34, np.array([[0, 2, 1, 3], [3, 0, 0, 1], [2, 2, 2, 2]])),
     {"axis": 1},
     lambda x, i: np.take_along_axis(x, i, axis=1))

_dil_img = rng.normal(size=(1, 6, 6, 2)).astype(F32)
_dil_w = (rng.normal(size=(3, 3, 2)) * 0.2).astype(F32)
case("dilation2d", "dilation2d", (_dil_img, _dil_w),
     {"strides": (1, 1), "rates": (1, 1), "padding": "SAME"},
     lambda x, w: _t(tf.nn.dilation2d, x, w, [1, 1, 1, 1], "SAME",
                     "NHWC", [1, 1, 1, 1]))
case("erosion2d", "erosion2d", (_dil_img, _dil_w),
     {"strides": (1, 1), "rates": (1, 1), "padding": "SAME"},
     lambda x, w: _t(tf.nn.erosion2d, x, w, [1, 1, 1, 1], "SAME",
                     "NHWC", [1, 1, 1, 1]))

_boxes = np.array([[0, 0, 1, 1], [0, 0, 0.9, 0.9], [0.5, 0.5, 1, 1],
                   [0, 0.6, 0.4, 1.0]], F32)
_scores = np.array([0.9, 0.8, 0.7, 0.6], F32)
case("nms_indices", "non_max_suppression", (_boxes, _scores),
     {"max_output_size": 4, "iou_threshold": 0.5},
     lambda b, s: np.concatenate([
         _t(tf.image.non_max_suppression, b, s, 4, 0.5),
         -np.ones(4 - len(_t(tf.image.non_max_suppression, b, s, 4, 0.5)),
                  np.int64)]),
     dtype_strict=False)

_cri = np.clip(rng.normal(size=(2, 6, 6, 3)).astype(F32), -1, 1)
_crb = np.array([[0.1, 0.1, 0.8, 0.8], [0.0, 0.0, 1.0, 0.5]], F32)
case("crop_and_resize", "crop_and_resize",
     (_cri, _crb, np.array([0, 1], I32)), {"crop_size": (4, 4)},
     lambda im, b, bi: _t(tf.image.crop_and_resize, im, b, bi, [4, 4]),
     rtol=1e-4, atol=1e-5)
case("embedding_lookup", "embedding_lookup",
     (x34, np.array([2, 0, 1, 2], I32)), {},
     lambda p, i: _t(tf.nn.embedding_lookup, p, i))
case("percentile_linear", "percentile", (x34,), {"q": 30.0, "axis": 1},
     lambda x: np.percentile(x, 30.0, axis=1).astype(np.float64),
     dtype_strict=False)
case("trapz", "trapz", (x34,), {"axis": 1},
     lambda y: np.trapezoid(y, axis=1) if hasattr(np, "trapezoid")
     else np.trapz(y, axis=1), dtype_strict=False)
case("bucketize", "bucketize",
     (np.array([-1., 0.5, 3., 10.], F32),),
     {"boundaries": [0.0, 1.0, 5.0]},
     lambda v: _t(lambda x: tf.raw_ops.Bucketize(
         input=x, boundaries=[0.0, 1.0, 5.0]), v), dtype_strict=False)



# ---- round-5 tranche: registry tail toward the 300-op gate ----------------
# (VERDICT r4 #7: push the sweep into the registry's remaining twinned tail)
v1l = tf.compat.v1.losses
MEAN = v1l.Reduction.MEAN

case("identity", "identity", (x34,), {}, lambda x: x)
case("rank_of", "rank", (x34,), {}, lambda x: _t(tf.rank, x),
     dtype_strict=False)
case("size_of", "size", (x34,), {}, lambda x: _t(tf.size, x),
     dtype_strict=False)
case("shape_of", "shape_of", (x34,), {}, lambda x: _t(tf.shape, x),
     dtype_strict=False)
case("matrix_transpose", "matrix_transpose",
     (rng.normal(size=(2, 3, 4)).astype(F32),), {},
     lambda x: _t(tf.linalg.matrix_transpose, x))
case("matrix_diag_part", "matrix_diag_part",
     (rng.normal(size=(2, 4, 3)).astype(F32),), {},
     lambda x: _t(tf.linalg.diag_part, x))
case("flip", "flip", (x34,), {"axis": (0,)},
     lambda x: _t(tf.reverse, x, [0]))
case("repeat_ax", "repeat", (x34,), {"repeats": 3, "axis": 1},
     lambda x: _t(tf.repeat, x, 3, axis=1))
case("tri", "tri", (4,), {"cols": 5, "diag": 1},
     lambda r: np.tri(4, 5, 1, dtype=np.float32), dtype_strict=False)
case("trilu_lower", "trilu", (x34,), {"k": 0, "upper": False},
     lambda x: _t(tf.linalg.band_part, x, -1, 0))
case("trilu_upper", "trilu", (x34,), {"k": 0, "upper": True},
     lambda x: _t(tf.linalg.band_part, x, 0, -1))
case("split", "split", (rng.normal(size=(6, 4)).astype(F32),),
     {"num_split": 3, "axis": 0},
     lambda x: _t(tf.split, x, 3, axis=0), out=(0, 1, 2))
case("split_v", "split_v", (rng.normal(size=(7, 4)).astype(F32),),
     {"size_splits": (2, 4, 1), "axis": 0},
     lambda x: _t(tf.split, x, [2, 4, 1], axis=0), out=(0, 1, 2))
case("unstack", "unstack", (rng.normal(size=(3, 4)).astype(F32),),
     {"axis": 0}, lambda x: _t(tf.unstack, x, axis=0), out=(0, 1, 2))
case("outer", "outer", (xn, yn), {},
     lambda a, b: _t(lambda u, v: tf.einsum("i,j->ij", u, v), a, b))
case("parallel_stack", "parallel_stack", (x34, x34 * 2, x34 - 1), {},
     lambda *xs: np.stack(xs))   # tf.parallel_stack refuses eager mode
case("dynamic_stitch", "dynamic_stitch",
     ([np.array([0, 2], I32), np.array([1, 3], I32)],
      [np.array([[1., 2.], [3., 4.]], F32),
       np.array([[5., 6.], [7., 8.]], F32)]), {},
     lambda i, v: _t(tf.dynamic_stitch, list(i), list(v)))
case("boolean_mask", "boolean_mask",
     (x34, np.array([True, False, True])), {},
     # ours is the STATIC-shape variant (XLA): compacted rows up front,
     # zero tail, count in output 1 — twin = tf result zero-padded
     lambda x, m: np.concatenate(
         [np.asarray(tf.boolean_mask(x, m)),
          np.zeros((int((~m).sum()),) + x.shape[1:], x.dtype)]))
case("where_np_cond", "where_np", (x34 > 0, x34, -x34), {},
     lambda c, x, y: _t(tf.where, c, x, y))
case("nonzero_coords", "nonzero_coords",
     (np.array([[0, 3, 0], [1, 0, 2]], I32),), {},
     # numpy nonzero layout (ndim, n) — the transpose of tf.where
     lambda x: np.stack(np.nonzero(x)), dtype_strict=False)
case("to_double", "to_double", (x34,), {},
     # jax_enable_x64=False narrows to f32 — values must still match
     lambda x: x.astype(np.float64), dtype_strict=False)
case("to_float16", "to_float16", (x34,), {},
     lambda x: x.astype(np.float16))
case("to_int64", "to_int64", (x34,), {},
     lambda x: x.astype(np.int64), dtype_strict=False)
case("cube", "cube", (x34,), {}, lambda x: _t(tf.pow, x, 3.0))
case("log2", "log2", (xpos,), {},
     lambda x: np.log2(x), rtol=1e-5, atol=1e-6)
case("log10", "log10", (xpos,), {},
     lambda x: np.log10(x), rtol=1e-5, atol=1e-6)
case("hard_tanh", "hard_tanh", (x34 * 3,), {},
     lambda x: _t(tf.clip_by_value, x, -1.0, 1.0))
case("hardmax", "hardmax", (x34,), {"axis": -1},
     lambda x: _t(lambda v: tf.one_hot(tf.argmax(v, -1), v.shape[-1]), x))
case("thresholdedrelu", "thresholdedrelu", (x34,), {"theta": 0.4},
     lambda x: np.where(x > 0.4, x, 0.0).astype(F32))
case("shrink", "shrink", (x34,), {"bias": 0.1, "lambd": 0.3},
     lambda x: np.where(x < -0.3, x + 0.1,
                        np.where(x > 0.3, x - 0.1, 0.0)).astype(F32))
case("prelu", "prelu", (x34, np.full((4,), 0.25, F32)), {},
     lambda x, a: np.where(x > 0, x, a * x).astype(F32))
case("crelu", "crelu", (x34,), {},
     lambda x: _t(tf.nn.crelu, x))
case("celu", "celu", (x34,), {"alpha": 1.2},
     lambda x: np.where(x > 0, x,
                        1.2 * np.expm1(x / 1.2)).astype(F32), rtol=1e-5,
     atol=1e-6)
case("mish", "mish", (x34,), {},
     lambda x: (x * np.tanh(np.log1p(np.exp(x)))).astype(F32),
     rtol=1e-5, atol=1e-6)
case("hard_swish", "hard_swish", (x34 * 3,), {},
     lambda x: (x * np.clip(x + 3, 0, 6) / 6).astype(F32),
     rtol=1e-5, atol=1e-6)
case("erfinv", "erfinv", (xunit,), {},
     lambda x: _t(tf.math.erfinv, x), rtol=1e-4, atol=1e-6)
case("popcount", "popcount", (ints,), {},
     lambda x: _t(lambda v: tf.raw_ops.PopulationCount(x=v), x),
     dtype_strict=False)
case("max_pairwise", "max_pairwise", (xn, yn), {},
     lambda a, b: _t(tf.maximum, a, b))
case("min_pairwise", "min_pairwise", (xn, yn), {},
     lambda a, b: _t(tf.minimum, a, b))
case("mergeadd", "mergeadd", (x34, x34 * 2, x34 - 1), {},
     lambda *xs: _t(tf.add_n, list(xs)))
case("mergeavg", "mergeavg", (x34, x34 * 2, x34 - 1), {},
     lambda *xs: _t(tf.add_n, list(xs)) / 3.0)
case("mergemax", "mergemax", (x34, x34 * 2, x34 - 1), {},
     lambda *xs: np.max(np.stack(xs), axis=0))
case("mergemaxindex", "mergemaxindex", (x34, x34 * 2, x34 - 1), {},
     lambda *xs: np.argmax(np.stack(xs), axis=0), dtype_strict=False)
case("rdiv", "rdiv", (xpos, x34), {}, lambda a, b: (b / a).astype(F32))
case("rsub", "rsub", (x34, xn[:3, None] * 0 + x34), {},
     lambda a, b: (b - a).astype(F32))
case("truncate_div", "truncate_div", (ints, intd), {},
     lambda a, b: _t(tf.truncatediv, a, b))
case("remainder", "remainder", (ints, intd), {},
     lambda a, b: np.remainder(a, b), dtype_strict=False)
case("axpy", "axpy", (x34, x34 * 0.5), {"a": 2.0},
     lambda x, y: (2.0 * x + y).astype(F32))
case("xw_plus_b", "xw_plus_b",
     (x34, rng.normal(size=(4, 5)).astype(F32),
      rng.normal(size=(5,)).astype(F32)), {},
     lambda x, w, b: _t(tf.compat.v1.nn.xw_plus_b, x, w, b),
     rtol=1e-5, atol=1e-6)
case("relu_layer", "relu_layer",
     (x34, rng.normal(size=(4, 5)).astype(F32),
      rng.normal(size=(5,)).astype(F32)), {},
     lambda x, w, b: _t(tf.compat.v1.nn.relu_layer, x, w, b),
     rtol=1e-5, atol=1e-6)
case("standardize", "standardize", (x34,), {"axis": -1},
     lambda x: ((x - x.mean(-1, keepdims=True))
                / x.std(-1, keepdims=True)).astype(F32),
     rtol=1e-4, atol=1e-5)
case("ones_like", "ones_like", (x34,), {}, lambda x: np.ones_like(x))
case("zeros_like", "zeros_like", (x34,), {}, lambda x: np.zeros_like(x))
case("stop_gradient", "stop_gradient", (x34,), {}, lambda x: x)



# ---- reductions / distances / segments (round-5 tranche B) ----------------
case("count_zero", "count_zero",
     (np.array([[0., 1., 0.], [2., 0., 3.]], F32),), {"axis": 1},
     lambda x: np.sum(x == 0, axis=1), dtype_strict=False)
case("entropy", "entropy", (np.array([0.5, 0.25, 0.25, 0.0], F32),), {},
     lambda p: np.float32(-np.sum(p[p > 0] * np.log(p[p > 0]))),
     rtol=1e-5, atol=1e-6)
case("shannon_entropy", "shannon_entropy",
     (np.array([0.5, 0.25, 0.25, 0.0], F32),), {},
     lambda p: np.float32(-np.sum(p[p > 0] * np.log2(p[p > 0]))),
     rtol=1e-5, atol=1e-6)
case("reduce_amax", "reduce_amax", (xn[~np.isnan(xn)],), {},
     lambda x: np.max(np.abs(x)))
case("reduce_amean", "reduce_amean", (x34,), {"axis": 1},
     lambda x: np.mean(np.abs(x), axis=1), rtol=1e-5, atol=1e-6)
case("reduce_asum", "reduce_asum", (x34,), {"axis": 0},
     lambda x: np.sum(np.abs(x), axis=0), rtol=1e-5, atol=1e-6)
case("reduce_norm1", "reduce_norm1", (x34,), {"axis": 1},
     lambda x: _t(tf.norm, x, ord=1, axis=1), rtol=1e-5, atol=1e-6)
case("reduce_norm2", "reduce_norm2", (x34,), {"axis": 1},
     lambda x: _t(tf.norm, x, ord=2, axis=1), rtol=1e-5, atol=1e-6)
case("reduce_sqnorm", "reduce_sqnorm", (x34,), {"axis": 1},
     lambda x: np.sum(x * x, axis=1), rtol=1e-5, atol=1e-6)
case("reduce_normmax", "reduce_normmax", (x34,), {"axis": 1},
     lambda x: _t(tf.norm, x, ord=np.inf, axis=1), rtol=1e-5, atol=1e-6)
case("reduce_stdev", "reduce_stdev", (x34,), {"axis": 1},
     lambda x: _t(tf.math.reduce_std, x, axis=1), rtol=1e-5, atol=1e-5)
case("reduce_stdev_corrected", "reduce_stdev", (x34,),
     {"axis": 1, "bias_corrected": True},
     lambda x: np.std(x, axis=1, ddof=1).astype(F32), rtol=1e-5, atol=1e-5)
case("reduce_variance", "reduce_variance", (x34,), {"axis": 0},
     lambda x: _t(tf.math.reduce_variance, x, axis=0),
     rtol=1e-5, atol=1e-5)
case("reduce_dot", "reduce_dot", (x34, x34 * 0.5), {"axis": 1},
     lambda a, b: np.sum(a * b, axis=1), rtol=1e-5, atol=1e-6)
case("reduce_logsumexp_axes", "reduce_logsumexp_axes", (x34,), {"axis": 1},
     lambda x: _t(tf.reduce_logsumexp, x, axis=1), rtol=1e-5, atol=1e-6)
case("histogram", "histogram", (x34,), {"num_bins": 5},
     lambda x: _t(tf.histogram_fixed_width, x,
                  [float(x.min()), float(x.max())], nbins=5),
     dtype_strict=False)
case("confusion_matrix", "confusion_matrix",
     (np.array([0, 1, 2, 2, 1], I32), np.array([0, 2, 2, 1, 1], I32)),
     {"num_classes": 3},
     lambda l, p: _t(tf.math.confusion_matrix, l, p, num_classes=3),
     dtype_strict=False)
case("segment_max", "segment_max",
     (np.array([1., 3., 2., 5., 4.], F32), np.array([0, 0, 1, 1, 2], I32)),
     {}, lambda d, s: _t(tf.math.segment_max, d, s))
case("segment_min", "segment_min",
     (np.array([1., 3., 2., 5., 4.], F32), np.array([0, 0, 1, 1, 2], I32)),
     {}, lambda d, s: _t(tf.math.segment_min, d, s))
case("segment_prod", "segment_prod",
     (np.array([1., 3., 2., 5., 4.], F32), np.array([0, 0, 1, 1, 2], I32)),
     {}, lambda d, s: _t(tf.math.segment_prod, d, s))
case("iamax", "iamax", (np.array([1., -7., 3., 7.], F32),), {},
     lambda x: np.argmax(np.abs(x)), dtype_strict=False)
case("iamin", "iamin", (np.array([1., -7., 3., -0.5], F32),), {},
     lambda x: np.argmin(np.abs(x)), dtype_strict=False)
case("argamax", "argamax", (x34,), {"axis": 1},
     lambda x: np.argmax(np.abs(x), axis=1), dtype_strict=False)
case("argamin", "argamin", (x34,), {"axis": 1},
     lambda x: np.argmin(np.abs(x), axis=1), dtype_strict=False)
case("dot_product", "dot_product", (xn[~np.isnan(xn)], yn[~np.isnan(yn)]),
     {}, lambda a, b: np.float32(np.dot(a, b)), rtol=1e-5, atol=1e-6)
case("cosine_similarity", "cosine_similarity", (x34, x34 * 0.5 + 0.1), {},
     lambda a, b: -_t(tf.keras.losses.cosine_similarity, a, b),
     rtol=1e-4, atol=1e-5)
case("euclidean_distance", "euclidean_distance", (x34, x34 * 0.5), {},
     lambda a, b: np.sqrt(np.sum((a - b) ** 2, -1)).astype(F32),
     rtol=1e-5, atol=1e-6)
case("manhattan_distance", "manhattan_distance", (x34, x34 * 0.5), {},
     lambda a, b: np.sum(np.abs(a - b), -1).astype(F32),
     rtol=1e-5, atol=1e-6)
case("is_non_decreasing_t", "is_non_decreasing",
     (np.array([1., 2., 2., 3.], F32),), {},
     lambda x: _t(tf.math.is_non_decreasing, x))
case("is_non_decreasing_f", "is_non_decreasing",
     (np.array([1., 2., 1.5], F32),), {},
     lambda x: _t(tf.math.is_non_decreasing, x))
case("is_strictly_increasing_edge", "is_strictly_increasing",
     (np.array([1., 2., 2.], F32),), {},
     lambda x: _t(tf.math.is_strictly_increasing, x))
case("is_numeric_tensor", "is_numeric_tensor", (x34,), {},
     lambda x: np.bool_(True), dtype_strict=False)

# ---- v1 loss-op family (ref: legacy loss declarables; twin = tf.compat.v1
# .losses with MEAN reduction) ---------------------------------------------
_lbl01 = rng.integers(0, 2, (4, 3)).astype(F32)
_pred = np.clip(rng.random((4, 3)).astype(F32), 0.05, 0.95)
_logits43 = rng.normal(size=(4, 3)).astype(F32)
case("hinge_loss", "hinge_loss", (_lbl01, _logits43), {},
     lambda l, p: _t(v1l.hinge_loss, l, p, reduction=MEAN),
     rtol=1e-5, atol=1e-6)
case("huber_loss", "huber_loss", (_lbl01, _pred), {"delta": 0.7},
     lambda l, p: _t(v1l.huber_loss, l, p, delta=0.7, reduction=MEAN),
     rtol=1e-5, atol=1e-6)
case("log_loss", "log_loss", (_lbl01, _pred), {},
     lambda l, p: _t(v1l.log_loss, l, p, reduction=MEAN),
     rtol=1e-4, atol=1e-5)
case("log_poisson_loss", "log_poisson_loss", (_logits43, _lbl01), {},
     lambda lo, t: _t(tf.nn.log_poisson_loss, t, lo),
     rtol=1e-5, atol=1e-6)
case("mean_sqerr_loss", "mean_sqerr_loss", (_lbl01, _pred), {},
     lambda l, p: _t(v1l.mean_squared_error, l, p, reduction=MEAN),
     rtol=1e-5, atol=1e-6)
case("absolute_difference_loss", "absolute_difference_loss",
     (_lbl01, _pred), {},
     lambda l, p: _t(v1l.absolute_difference, l, p, reduction=MEAN),
     rtol=1e-5, atol=1e-6)
case("softmax_cross_entropy", "softmax_cross_entropy",
     (_logits43, _lbl01 / np.maximum(_lbl01.sum(-1, keepdims=True), 1)), {},
     lambda lo, l: _t(tf.nn.softmax_cross_entropy_with_logits,
                      labels=l, logits=lo), rtol=1e-5, atol=1e-6)
case("sparse_softmax_cross_entropy", "sparse_softmax_cross_entropy",
     (_logits43, np.array([0, 2, 1, 0], I32)), {},
     lambda lo, l: _t(tf.nn.sparse_softmax_cross_entropy_with_logits,
                      labels=l, logits=lo), rtol=1e-5, atol=1e-6)
case("mean_pairwssqerr_loss", "mean_pairwssqerr_loss", (_pred, _lbl01), {},
     lambda p, l: _t(v1l.mean_pairwise_squared_error, l, p),
     rtol=1e-4, atol=1e-5)
case("cosine_distance_loss", "cosine_distance_loss",
     (_pred / np.linalg.norm(_pred, axis=-1, keepdims=True),
      _lbl01 / np.maximum(np.linalg.norm(_lbl01, axis=-1, keepdims=True),
                          1e-6)), {},
     lambda l, p: _t(v1l.cosine_distance, l, p, axis=-1, reduction=MEAN),
     rtol=1e-4, atol=1e-5)



# ---- nn / image / structural (round-5 tranche C) --------------------------
vol = rng.normal(size=(1, 4, 6, 6, 2)).astype(F32)
case("maxpool3d", "maxpool3d", (vol,),
     {"kernel": (2, 2, 2), "strides": (2, 2, 2), "padding": "VALID"},
     lambda x: _t(tf.nn.max_pool3d, x, (2, 2, 2), (2, 2, 2), "VALID"))
case("avgpool3d", "avgpool3d", (vol,),
     {"kernel": (2, 2, 2), "strides": (2, 2, 2), "padding": "VALID"},
     lambda x: _t(tf.nn.avg_pool3d, x, (2, 2, 2), (2, 2, 2), "VALID"),
     rtol=1e-5, atol=1e-6)
case("conv1d", "conv1d",
     (rng.normal(size=(2, 8, 3)).astype(F32),
      rng.normal(size=(3, 3, 4)).astype(F32)),
     {"stride": 1, "padding": "SAME"},
     lambda x, w: _t(tf.nn.conv1d, x, w, 1, "SAME"),
     rtol=1e-4, atol=1e-5)
case("conv3d", "conv3d",
     (vol, rng.normal(size=(2, 2, 2, 2, 3)).astype(F32)),
     {"strides": (1, 1, 1), "padding": "SAME"},
     lambda x, w: _t(tf.nn.conv3d, x, w, (1, 1, 1, 1, 1), "SAME"),
     rtol=1e-4, atol=1e-4)
case("fused_batch_norm_train", "fused_batch_norm",
     (rng.normal(size=(2, 4, 4, 3)).astype(F32),
      np.array([1.0, 1.2, 0.8], F32), np.array([0.1, -0.1, 0.0], F32)),
     {"epsilon": 1e-3, "is_training": True},
     lambda x, s, o: _t(lambda a, b, c: tf.compat.v1.nn.fused_batch_norm(
         a, b, c, epsilon=1e-3, is_training=True)[0], x, s, o),
     rtol=1e-4, atol=1e-5, out=0)
case("normalize_moments", "normalize_moments",
     (np.float32(10.0), np.array([5., 10.], F32),
      np.array([20., 60.], F32)), {},
     lambda c, m, v: _t(lambda cc, mm, vv: tf.nn.normalize_moments(
         cc, mm, vv, shift=None), c, m, v),
     out=(0, 1), rtol=1e-5, atol=1e-6)
case("sufficient_statistics", "sufficient_statistics", (x34,),
     {"axes": (0,)},
     lambda x: [np.float32(x.shape[0]), x.sum(0), (x * x).sum(0)],
     out=(0, 1, 2), rtol=1e-5, atol=1e-5)
case("space_to_batch", "space_to_batch",
     (rng.normal(size=(1, 4, 4, 1)).astype(F32),),
     {"block_size": 2, "paddings": ((0, 0), (0, 0))},
     lambda x: _t(tf.compat.v1.space_to_batch, x, [[0, 0], [0, 0]], 2))
case("batch_to_space", "batch_to_space",
     (rng.normal(size=(4, 2, 2, 1)).astype(F32),),
     {"block_size": 2, "crops": ((0, 0), (0, 0))},
     lambda x: _t(tf.compat.v1.batch_to_space, x, [[0, 0], [0, 0]], 2))
case("space_to_batch_nd", "space_to_batch_nd",
     (rng.normal(size=(1, 4, 6, 1)).astype(F32),),
     {"block_shape": (2, 3), "paddings": ((0, 0), (0, 0))},
     lambda x: _t(tf.space_to_batch_nd, x, [2, 3], [[0, 0], [0, 0]]))
case("batch_to_space_nd", "batch_to_space_nd",
     (rng.normal(size=(6, 2, 2, 1)).astype(F32),),
     {"block_shape": (2, 3), "crops": ((0, 0), (0, 0))},
     lambda x: _t(tf.batch_to_space, x, [2, 3], [[0, 0], [0, 0]]))
case("sparse_to_dense", "sparse_to_dense",
     (np.array([[0, 1], [2, 3]], I32), np.array([5., 7.], F32)),
     {"dense_shape": (3, 4), "default_value": -1.0},
     lambda i, v: _t(lambda ii, vv: tf.raw_ops.SparseToDense(
         sparse_indices=ii, output_shape=[3, 4], sparse_values=vv,
         default_value=-1.0), i, v))
case("fill_dynamic", "fill_dynamic", (np.array([2, 3], I32),),
     {"value": 2.5}, lambda d: _t(tf.fill, d, 2.5))
case("ifft2", "ifft2",
     ((rng.normal(size=(4, 4)) + 1j * rng.normal(size=(4, 4)))
      .astype(np.complex64),), {},
     lambda x: np.fft.ifft2(x).astype(np.complex64), rtol=1e-4, atol=1e-5)
case("fake_quant_args", "fake_quant_with_min_max_args", (x34 * 4,),
     {"min": -3.0, "max": 3.0, "num_bits": 8},
     lambda x: _t(tf.quantization.fake_quant_with_min_max_args, x,
                  min=-3.0, max=3.0, num_bits=8), rtol=1e-5, atol=1e-6)
case("yiq_to_rgb", "yiq_to_rgb",
     (np.clip(rng.random((1, 4, 4, 3)).astype(F32), 0, 1),), {},
     lambda x: _t(tf.image.yiq_to_rgb, x), rtol=1e-3, atol=3e-4)
case("yuv_to_rgb", "yuv_to_rgb",
     (np.stack([np.clip(rng.random((4, 4)), 0.2, 0.8),
                rng.random((4, 4)) * 0.1 - 0.05,
                rng.random((4, 4)) * 0.1 - 0.05], -1)[None].astype(F32),),
     {}, lambda x: _t(tf.image.yuv_to_rgb, x), rtol=1e-4, atol=1e-4)
case("upsampling2d", "upsampling2d",
     (rng.normal(size=(1, 3, 4, 2)).astype(F32),), {"size": 2},
     lambda x: np.repeat(np.repeat(x, 2, 1), 2, 2))
case("maxpool_with_argmax", "maxpool_with_argmax",
     (rng.normal(size=(1, 4, 4, 2)).astype(F32),),
     {"kernel": (2, 2), "strides": (2, 2), "padding": "VALID"},
     lambda x: [np.asarray(r) for r in tf.nn.max_pool_with_argmax(
         x, (2, 2), (2, 2), "VALID")],
     out=(0, 1), dtype_strict=False)

# ---- activation derivatives vs tf.GradientTape (the _bp/-derivative
# family: our closed forms must equal TF autodiff at grad-out = 1) ---------
def _tape(fn, x, **kw):
    t = tf.constant(x)
    with tf.GradientTape() as g:
        g.watch(t)
        y = fn(t, **kw)
    return np.asarray(g.gradient(y, t))


xd = np.array([-2.5, -1.0, -0.3, 0.0, 0.3, 1.0, 2.5], F32)
case("tanh_derivative", "tanh_derivative", (xd,), {},
     lambda x: _tape(tf.tanh, x), rtol=1e-5, atol=1e-6)
case("sigmoid_derivative", "sigmoid_derivative", (xd,), {},
     lambda x: _tape(tf.sigmoid, x), rtol=1e-5, atol=1e-6)
case("relu_derivative", "relu_derivative", (xd,), {},
     lambda x: _tape(tf.nn.relu, x))
case("relu6_derivative", "relu6_derivative", (np.array(
     [-1., 0.5, 3.0, 5.9, 6.5], F32),), {},
     lambda x: _tape(tf.nn.relu6, x))
case("elu_derivative", "elu_derivative", (xd,), {},
     lambda x: _tape(tf.nn.elu, x), rtol=1e-5, atol=1e-6)
# x=0 excluded: at the boundary the reference picks the negative branch
# (alpha·scale) where TF's SeluGrad picks scale — both defensible
case("selu_derivative", "selu_derivative",
     (xd[np.abs(xd) > 0],), {},
     lambda x: _tape(tf.nn.selu, x), rtol=1e-5, atol=1e-6)
case("softplus_derivative", "softplus_derivative", (xd,), {},
     lambda x: _tape(tf.nn.softplus, x), rtol=1e-5, atol=1e-6)
case("softsign_derivative", "softsign_derivative", (xd,), {},
     lambda x: _tape(tf.nn.softsign, x), rtol=1e-5, atol=1e-6)
case("swish_derivative", "swish_derivative", (xd,), {},
     lambda x: _tape(tf.nn.silu, x), rtol=1e-5, atol=1e-6)
case("mish_derivative", "mish_derivative", (xd,), {},
     lambda x: _tape(lambda t: t * tf.tanh(tf.nn.softplus(t)), x),
     rtol=1e-4, atol=1e-5)
case("cube_derivative", "cube_derivative", (xd,), {},
     lambda x: _tape(lambda t: tf.pow(t, 3.0), x), rtol=1e-5, atol=1e-5)
# |x|=1 excluded: ours takes the subgradient midpoint 0.5 at the kink,
# TF's clip grad picks 1 — conventions differ only exactly at the corner
case("hardtanh_derivative", "hardtanh_derivative",
     (np.array([-2.5, -0.99, -0.3, 0.0, 0.3, 0.99, 2.5], F32),), {},
     lambda x: _tape(lambda t: tf.clip_by_value(t, -1.0, 1.0), x))

# ---- round-5 tranche 2: normalization / BLAS / scatter / bit ops ----------
# (VERDICT r4 #7 follow-through past the 300 gate: the remaining registry
# tail with deterministic ecosystem twins — TF where TF has the op, numpy
# manual math where numpy IS the twin.)
x234 = rng.normal(size=(2, 3, 4)).astype(F32)
xr4 = rng.normal(size=(4,)).astype(F32)
xi32 = rng.integers(-1 << 20, 1 << 20, size=(6,), dtype=np.int32)

case("biasadd_nhwc", "biasadd",
     (rng.normal(size=(2, 3, 4, 5)).astype(F32),
      rng.normal(size=(5,)).astype(F32)), {},
     lambda x, b: _t(tf.nn.bias_add, x, b))
case("biasadd_nchw", "biasadd",
     (rng.normal(size=(2, 5, 3, 4)).astype(F32),
      rng.normal(size=(5,)).astype(F32)), {"data_format": "NCHW"},
     lambda x, b: _t(tf.nn.bias_add, x, b, data_format="NCHW"))
case("batchnorm_inference", "batchnorm",
     (rng.normal(size=(2, 3, 4)).astype(F32), xr4, np.abs(xr4) + 0.2,
      xr4 * 0.5 + 1.0, xr4 - 0.3), {"epsilon": 1e-3},
     lambda x, m, v, g, b: _t(tf.nn.batch_normalization, x, m, v, b, g,
                              1e-3), rtol=1e-5, atol=1e-5)
case("layer_norm_last", "layer_norm",
     (x234, xr4 * 0.5 + 1.0, xr4 - 0.3), {"epsilon": 1e-5},
     lambda x, g, b: ((x - x.mean(-1, keepdims=True))
                      / np.sqrt(x.var(-1, keepdims=True) + 1e-5)) * g + b,
     rtol=1e-5, atol=1e-5)
case("group_norm", "group_norm",
     (rng.normal(size=(2, 6, 5)).astype(F32),
      rng.normal(size=(6,)).astype(F32),
      rng.normal(size=(6,)).astype(F32)), {"num_groups": 3},
     lambda x, g, b: (lambda xg: (((xg - xg.mean((2, 3), keepdims=True))
                                   / np.sqrt(xg.var((2, 3), keepdims=True)
                                             + 1e-5)).reshape(x.shape)
                                  * g.reshape(1, 6, 1) + b.reshape(1, 6, 1)))
     (x.reshape(2, 3, 2, 5)), rtol=1e-5, atol=1e-5)
case("norm_fro", "norm", (x34,), {},
     lambda x: np.linalg.norm(x).astype(F32))
case("norm_axis", "norm", (x34,), {"axis": 1},
     lambda x: np.linalg.norm(x, axis=1).astype(F32))
case("clip_global_norm_multi", "clip_by_global_norm",
     (x34, xr4), {"clip_norm": 0.5},
     lambda a, b: _t(lambda u, v: tf.clip_by_global_norm([u, v], 0.5)[0],
                     a, b), out=(0, 1))
case("clip_avg_norm", "clip_by_avg_norm", (x34,), {"clip_norm": 0.1},
     lambda x: _t(tf.compat.v1.clip_by_average_norm, x, 0.1))
case("gemm_trans_beta", "gemm",
     (rng.normal(size=(5, 3)).astype(F32),
      rng.normal(size=(5, 4)).astype(F32),
      rng.normal(size=(3, 4)).astype(F32)),
     {"alpha": 1.5, "beta": 0.5, "transA": True},
     lambda a, b, c: (1.5 * a.T @ b + 0.5 * c).astype(F32),
     rtol=1e-5, atol=1e-5)
case("gemv", "gemv",
     (rng.normal(size=(3, 4)).astype(F32), xr4,
      rng.normal(size=(3,)).astype(F32)), {"alpha": 2.0, "beta": 1.0},
     lambda a, x, y: (2.0 * a @ x + y).astype(F32), rtol=1e-5, atol=1e-5)
case("batched_gemm", "batched_gemm",
     (rng.normal(size=(2, 3, 4)).astype(F32),
      rng.normal(size=(2, 4, 5)).astype(F32)), {},
     lambda a, b: np.matmul(a, b), rtol=1e-5, atol=1e-5)
case("euclidean_r3", "euclidean", (x34, x34[::-1].copy(), 1), {},
     lambda x, y, d: np.sqrt(np.sum((x - y) ** 2, axis=d)).astype(F32))
case("manhattan_r3", "manhattan", (x34, x34[::-1].copy(), 0), {},
     lambda x, y, d: np.sum(np.abs(x - y), axis=d).astype(F32))
case("cosinedistance_r3", "cosinedistance", (x34, x34 * 0.5 + 0.1, 1), {},
     lambda x, y, d: (1.0 - np.sum(x * y, 1)
                      / (np.linalg.norm(x, axis=1)
                         * np.linalg.norm(y, axis=1))).astype(F32),
     rtol=1e-5, atol=1e-6)
case("hammingdistance_r3", "hammingdistance",
     (np.array([1., 2., 3., 4.], F32), np.array([1., 0., 3., 0.], F32)), {},
     lambda x, y: np.float32(2.0))
case("first_index_none_match", "first_index",
     (np.array([-1., -2., -3.], F32),), {"condition": "gt", "value": 0.0},
     lambda x: np.int64(-1), dtype_strict=False)
case("last_index_gt", "last_index",
     (np.array([1., -2., 3., -4., 5., -6.], F32),),
     {"condition": "gt", "value": 0.0},
     lambda x: np.int64(4), dtype_strict=False)
case("match_condition_count", "match_condition",
     (np.array([1., -2., 3., -4., 5., -6.], F32),),
     {"condition": "lt", "value": 0.0},
     lambda x: np.int64(3), dtype_strict=False)
case("scatter_mul", "scatter_mul",
     (np.arange(1, 13, dtype=F32).reshape(4, 3),
      np.array([0, 2], I32), np.full((2, 3), 2.0, F32)), {},
     lambda r, i, u: (lambda o: (o.__setitem__(i, o[i] * u), o)[1])
     (r.copy()))
case("scatter_div", "scatter_div",
     (np.arange(1, 13, dtype=F32).reshape(4, 3),
      np.array([1, 3], I32), np.full((2, 3), 4.0, F32)), {},
     lambda r, i, u: (lambda o: (o.__setitem__(i, o[i] / u), o)[1])
     (r.copy()))
case("scatter_nd_add", "scatter_nd_add",
     (np.zeros((4, 3), F32), np.array([[0], [2], [0]], I32),
      np.ones((3, 3), F32)), {},
     lambda r, i, u: _t(tf.tensor_scatter_nd_add, r, i, u))
case("scatter_nd_sub", "scatter_nd_sub",
     (np.ones((4, 3), F32), np.array([[1], [3]], I32),
      np.full((2, 3), 0.5, F32)), {},
     lambda r, i, u: _t(tf.tensor_scatter_nd_sub, r, i, u))
case("scatter_nd_update", "scatter_nd_update",
     (np.zeros((4, 3), F32), np.array([[2], [0]], I32),
      np.stack([np.full(3, 7.0, F32), np.full(3, 9.0, F32)])), {},
     lambda r, i, u: _t(tf.tensor_scatter_nd_update, r, i, u))
case("scatter_elements_add", "scatter_elements",
     (np.zeros((3, 4), F32), np.array([[0, 1], [1, 2], [2, 0]], I32),
      np.arange(1, 7, dtype=F32).reshape(3, 2)),
     {"axis": 1, "reduction": "add"},
     lambda x, i, u: (lambda o: ([o.__setitem__(
         (r, i[r, c]), o[r, i[r, c]] + u[r, c])
         for r in range(3) for c in range(2)], o)[1])(x.copy()))
case("toggle_bits", "toggle_bits", (xi32,), {},
     lambda x: np.bitwise_not(x))
case("cyclic_shift_bits", "cyclic_shift_bits", (xi32, 5), {},
     lambda x, s: (lambda u: ((u << s) | (u >> (32 - s))).astype(np.int32))
     (x.view(np.uint32)))
case("bits_hamming", "bits_hamming_distance",
     (np.array([0b1011, 0b0110], np.int32),
      np.array([0b0011, 0b0101], np.int32)), {},
     lambda a, b: np.int32(np.unpackbits(
         (a ^ b).view(np.uint8)).sum()), dtype_strict=False)
case("bitcast_f32_i32", "bitcast", (x34,), {"dtype": jnp.int32},
     lambda x: _t(tf.bitcast, x, tf.int32))
case("compare_and_bitpack", "compare_and_bitpack",
     (rng.normal(size=(2, 16)).astype(F32), 0.0), {},
     lambda x, t: np.packbits((x > t), axis=-1))
case("fake_quant_vars", "fake_quant_with_min_max_vars",
     (np.linspace(-8.0, 8.0, 13, dtype=F32), np.float32(-6.0),
      np.float32(6.0)), {"num_bits": 8},
     lambda x, lo, hi: _t(tf.quantization.fake_quant_with_min_max_vars,
                          x, lo, hi, num_bits=8), rtol=1e-5, atol=1e-5)
case("quantize_roundtrip", "quantize",
     (np.linspace(-1.0, 1.0, 9, dtype=F32), -1.0, 1.0), {"num_bits": 8},
     lambda x, lo, hi: np.clip(np.round((x - lo) / ((hi - lo) / 255.0)),
                               0, 255).astype(np.int32))
case("dequantize", "dequantize",
     (np.array([0, 64, 128, 255], np.int32), -1.0, 1.0), {"num_bits": 8},
     lambda q, lo, hi: (q.astype(F32) * ((hi - lo) / 255.0) + lo))
case("im2col", "im2col",
     (rng.normal(size=(1, 5, 6, 3)).astype(F32),),
     {"kernel": (2, 3), "strides": (1, 2), "padding": "VALID"},
     lambda x: (lambda p: p.reshape(p.shape[:3] + (2, 3, 3))
                .transpose(0, 1, 2, 5, 3, 4)
                .reshape(p.shape))(
         _t(tf.image.extract_patches, x, [1, 2, 3, 1], [1, 1, 2, 1],
            [1, 1, 1, 1], "VALID")))
case("upsampling3d", "upsampling3d",
     (rng.normal(size=(1, 2, 3, 2, 4)).astype(F32),), {"scale": 2},
     lambda x: x.repeat(2, 1).repeat(2, 2).repeat(2, 3))
case("maxout", "maxout", (rng.normal(size=(3, 8)).astype(F32),),
     {"channels": 2}, lambda x: x.reshape(3, 4, 2).max(-1))
case("pnormpool2d", "pnormpool2d",
     (np.abs(rng.normal(size=(1, 4, 4, 2))).astype(F32),),
     {"kernel": (2, 2), "pnorm": 3},
     lambda x: (x.reshape(1, 2, 2, 2, 2, 2).transpose(0, 1, 3, 2, 4, 5)
                .reshape(1, 2, 2, 4, 2) ** 3).sum(3) ** (1 / 3),
     rtol=1e-5, atol=1e-5)
case("maxpool2d_nchw", "maxpool2d_nchw",
     (rng.normal(size=(1, 3, 4, 6)).astype(F32),),
     {"kernel": (2, 2), "strides": (2, 2)},
     lambda x: _t(lambda t: tf.transpose(tf.nn.max_pool2d(
         tf.transpose(t, [0, 2, 3, 1]), 2, 2, "VALID"), [0, 3, 1, 2]), x))
case("avgpool2d_nchw", "avgpool2d_nchw",
     (rng.normal(size=(1, 3, 4, 6)).astype(F32),),
     {"kernel": (2, 2), "strides": (2, 2)},
     lambda x: _t(lambda t: tf.transpose(tf.nn.avg_pool2d(
         tf.transpose(t, [0, 2, 3, 1]), 2, 2, "VALID"), [0, 3, 1, 2]), x))
case("global_avgpool2d", "global_avgpool2d",
     (rng.normal(size=(2, 3, 4, 5)).astype(F32),), {},
     lambda x: x.mean((1, 2)))
case("matrix_power", "matrix_power",
     (rng.normal(size=(3, 3)).astype(F32) * 0.5,), {"n": 3},
     lambda x: np.linalg.matrix_power(x, 3), rtol=1e-4, atol=1e-5)
case("log_matrix_determinant", "log_matrix_determinant",
     (np.array([[2., 1.], [1., 3.]], F32) + np.eye(2, dtype=F32),), {},
     lambda x: [np.linalg.slogdet(x)[0].astype(F32),
                np.linalg.slogdet(x)[1].astype(F32)],
     out=(0, 1), rtol=1e-5, atol=1e-6)
case("matrix_rank", "matrix_rank",
     (np.array([[1., 2., 3.], [2., 4., 6.], [0., 1., 0.]], F32),), {},
     lambda x: np.linalg.matrix_rank(x), dtype_strict=False)
case("pinv", "pinv", (rng.normal(size=(4, 3)).astype(F32),), {},
     lambda x: np.linalg.pinv(x).astype(F32), rtol=1e-3, atol=1e-4)
case("lstsq", "lstsq",
     (rng.normal(size=(5, 3)).astype(F32),
      rng.normal(size=(5, 2)).astype(F32)), {},
     lambda a, b: np.linalg.lstsq(a, b, rcond=None)[0].astype(F32),
     rtol=1e-3, atol=1e-4)
case("reduce_amin", "reduce_amin",
     (np.array([[-5., 2.], [3., -1.]], F32),), {"axis": 1},
     lambda x: np.min(np.abs(x), 1))
case("reduce_norm_max", "reduce_norm_max",
     (np.array([[-5., 2.], [3., -1.]], F32),), {},
     lambda x: np.float32(5.0))
case("reversemod", "reversemod", (intd, ints), {},
     lambda x, y: np.mod(y, x))
case("to_float32", "to_float32", (ints,), {}, lambda x: x.astype(F32))
case("to_uint32", "to_uint32",
     (np.array([0, 1, 7], np.int32),), {},
     lambda x: x.astype(np.uint32))
case("ones_as", "ones_as", (x34,), {}, lambda x: np.ones_like(x))
case("zeros_as", "zeros_as", (ints,), {}, lambda x: np.zeros_like(x))
case("size_at", "size_at", (x34,), {"dim": 1},
     lambda x: np.int64(4), dtype_strict=False)
case("shapes_of", "shapes_of", (x34, xr4), {},
     lambda a, b: [np.asarray(a.shape), np.asarray(b.shape)],
     out=(0, 1), dtype_strict=False)
case("order_c", "order", (x34,), {"order": "c"}, lambda x: x)
case("choose_gt", "choose",
     (np.array([3., -1., 4., -1., 5., -9.], F32),),
     {"scalar": 0.0, "mode": 1},
     lambda x: [np.array([3., 4., 5., 0., 0., 0.], F32), np.int32(3)],
     out=(0, 1))
case("tear_rows", "tear", (x34, 1), {},
     lambda x, d: [x[0], x[1], x[2]], out=(0, 1, 2))
case("assign_add", "assign_add", (x34, x34 * 2), {},
     lambda x, y: x + y)
case("assign_sub", "assign_sub", (x34, x34 * 0.5), {},
     lambda x, y: (x - x * 0.5).astype(F32))
case("set_scalar", "set_scalar", (x34,), {"value": 2.5},
     lambda x: np.full_like(x, 2.5))
case("check_numerics_finite", "check_numerics", (x34,), {},
     lambda x: _t(tf.debugging.check_numerics, x, "conformance"))
case("image_resize_area_int", "image_resize",
     (rng.normal(size=(1, 8, 8, 2)).astype(F32), (4, 4)),
     {"method": "area"},
     lambda x, s: _t(tf.image.resize, x, s, method="area"),
     rtol=1e-5, atol=1e-6)
case("resize_area_int", "resize_area",
     (rng.normal(size=(1, 8, 8, 2)).astype(F32), (4, 4)), {},
     lambda x, s: _t(tf.image.resize, x, s, method="area"),
     rtol=1e-5, atol=1e-6)
case("max_unpool", "max_unpool",
     (np.array([[[5., 7.]]], F32).reshape(1, 1, 1, 2),
      np.array([2, 5], np.int32).reshape(1, 1, 1, 2), (1, 1, 2, 3)), {},
     lambda p, i, s: np.array([[[[0., 0., 5.], [0., 0., 7.]]]], F32))
case("sparse_dense_matmul", "sparse_dense_matmul",
     (np.array([[0, 1], [1, 0], [2, 2]], np.int64),
      np.array([2., 3., 4.], F32), (3, 3),
      rng.normal(size=(3, 2)).astype(F32)), {},
     lambda i, v, s, b: _t(
         lambda: tf.sparse.sparse_dense_matmul(
             tf.SparseTensor(i, v, s), b)), rtol=1e-5, atol=1e-6)
case("broadcast_dynamic_shape", "broadcast_dynamic_shape",
     (np.array([3, 1, 4], I32), np.array([3, 4], I32)), {},
     lambda a, b: _t(tf.broadcast_dynamic_shape, a, b),
     dtype_strict=False)


# ---- round-5 tranche 3: the _bp family vs tf.GradientTape -----------------
# Registry _bp ops take (forward inputs..., upstream gradient) and return
# the input cotangents; the TF twin is GradientTape with output_gradients.
# Gradients are where silent divergence hides (SAME-padding asymmetry,
# pool tie-breaks, normalization statistics terms).
def _tape_g(fn, g, *xs):
    ts = [tf.constant(x) for x in xs]
    with tf.GradientTape() as tp:
        for t in ts:
            tp.watch(t)
        y = fn(*ts)
    out = tp.gradient(y, ts, output_gradients=tf.constant(g))
    return [np.asarray(o) for o in out]


g775 = rng.normal(size=(1, 7, 7, 5)).astype(F32)
case("conv2d_bp", "conv2d_bp", (img, ker, g775),
     {"strides": (1, 1), "padding": "SAME"},
     lambda x, k, g: _tape_g(
         lambda a, b: tf.nn.conv2d(a, b, [1, 1, 1, 1], "SAME"), g, x, k),
     out=(0, 1), rtol=1e-4, atol=1e-4)
case("conv1d_bp", "conv1d_bp",
     (rng.normal(size=(2, 8, 3)).astype(F32),
      rng.normal(size=(3, 3, 4)).astype(F32),
      rng.normal(size=(2, 8, 4)).astype(F32)),
     {"stride": 1, "padding": "SAME"},
     lambda x, w, g: _tape_g(
         lambda a, b: tf.nn.conv1d(a, b, 1, "SAME"), g, x, w),
     out=(0, 1), rtol=1e-4, atol=1e-4)
vol3 = rng.normal(size=(1, 3, 4, 4, 2)).astype(F32)
ker3 = rng.normal(size=(2, 2, 2, 2, 3)).astype(F32) * 0.3
case("conv3d_bp", "conv3d_bp",
     (vol3, ker3, rng.normal(size=(1, 3, 4, 4, 3)).astype(F32)),
     {"strides": (1, 1, 1), "padding": "SAME"},
     lambda x, w, g: _tape_g(
         lambda a, b: tf.nn.conv3d(a, b, (1, 1, 1, 1, 1), "SAME"), g, x, w),
     out=(0, 1), rtol=1e-4, atol=1e-4)
case("depthwise_conv2d_bp", "depthwise_conv2d_bp",
     (img, dker, rng.normal(size=(1, 7, 7, 6)).astype(F32)),
     {"strides": (1, 1), "padding": "SAME"},
     lambda x, k, g: _tape_g(
         lambda a, b: tf.nn.depthwise_conv2d(a, b, [1, 1, 1, 1], "SAME"),
         g, x, k),
     out=(0, 1), rtol=1e-4, atol=1e-4)
g443 = rng.normal(size=(1, 4, 4, 3)).astype(F32)
case("maxpool2d_bp", "maxpool2d_bp", (img, g443),
     {"kernel": (3, 3), "strides": (2, 2), "padding": "SAME"},
     lambda x, g: _tape_g(
         lambda t: tf.nn.max_pool2d(t, 3, 2, "SAME"), g, x)[0],
     rtol=1e-5, atol=1e-6)
case("maxpool2d_bp_ties", "maxpool2d_bp",
     (np.ones((1, 4, 4, 1), F32),
      rng.normal(size=(1, 2, 2, 1)).astype(F32)),
     {"kernel": (2, 2), "strides": (2, 2), "padding": "VALID"},
     lambda x, g: _tape_g(
         lambda t: tf.nn.max_pool2d(t, 2, 2, "VALID"), g, x)[0],
     rtol=1e-6, atol=0)
case("avgpool2d_bp", "avgpool2d_bp", (img, g443),
     {"kernel": (3, 3), "strides": (2, 2), "padding": "SAME"},
     lambda x, g: _tape_g(
         lambda t: tf.nn.avg_pool2d(t, 3, 2, "SAME"), g, x)[0],
     rtol=1e-5, atol=1e-6)
vol4 = rng.normal(size=(1, 4, 4, 4, 2)).astype(F32)
g222 = rng.normal(size=(1, 2, 2, 2, 2)).astype(F32)
case("maxpool3d_bp", "maxpool3d_bp", (vol4, g222),
     {"kernel": (2, 2, 2), "strides": (2, 2, 2), "padding": "VALID"},
     lambda x, g: _tape_g(
         lambda t: tf.nn.max_pool3d(t, 2, 2, "VALID"), g, x)[0],
     rtol=1e-5, atol=1e-6)
case("avgpool3d_bp", "avgpool3d_bp", (vol4, g222),
     {"kernel": (2, 2, 2), "strides": (2, 2, 2), "padding": "VALID"},
     lambda x, g: _tape_g(
         lambda t: tf.nn.avg_pool3d(t, 2, 2, "VALID"), g, x)[0],
     rtol=1e-5, atol=1e-6)
xlrn = rng.normal(size=(1, 4, 4, 8)).astype(F32)
case("lrn_bp", "lrn_bp", (xlrn, rng.normal(size=(1, 4, 4, 8)).astype(F32)),
     {"depth_radius": 2, "bias": 1.0, "alpha": 1e-3, "beta": 0.75},
     lambda x, g: _tape_g(
         lambda t: tf.nn.local_response_normalization(
             t, depth_radius=2, bias=1.0, alpha=1e-3, beta=0.75), g, x)[0],
     rtol=1e-4, atol=1e-5)
gln = rng.normal(size=(2, 3, 4)).astype(F32)
case("layer_norm_bp", "layer_norm_bp",
     (x234, xr4 * 0.5 + 1.0, xr4 - 0.3, gln),
     {"axis": -1, "epsilon": 1e-5},
     lambda x, ga, be, g: _tape_g(
         lambda t, w, b: (t - tf.reduce_mean(t, -1, keepdims=True))
         * tf.math.rsqrt(tf.math.reduce_variance(t, -1, keepdims=True)
                         + 1e-5) * w + b, g, x, ga, be),
     out=(0, 1, 2), rtol=1e-4, atol=1e-4)
case("batchnorm_bp", "batchnorm_bp",
     (x234, xr4, np.abs(xr4) + 0.2, xr4 * 0.5 + 1.0, xr4 - 0.3, gln),
     {"epsilon": 1e-3},
     lambda x, m, v, ga, be, g: _tape_g(
         lambda t, w, b: tf.nn.batch_normalization(t, m, v, b, w, 1e-3),
         g, x, ga, be),
     out=(0, 1, 2), rtol=1e-4, atol=1e-4)
case("biasadd_bp", "biasadd_bp",
     (rng.normal(size=(2, 3, 4, 5)).astype(F32),
      rng.normal(size=(5,)).astype(F32),
      rng.normal(size=(2, 3, 4, 5)).astype(F32)), {},
     lambda x, b, g: _tape_g(tf.nn.bias_add, g, x, b),
     out=(0, 1), rtol=1e-5, atol=1e-6)
xsm = rng.normal(size=(2, 3, 2, 4)).astype(F32)
gsm = rng.normal(size=(2, 3, 2, 4)).astype(F32)
case("upsampling2d_bp", "upsampling2d_bp",
     (rng.normal(size=(2, 2, 3, 2)).astype(F32),
      rng.normal(size=(2, 4, 6, 2)).astype(F32)), {"size": 2},
     lambda x, g: _tape_g(
         lambda t: tf.repeat(tf.repeat(t, 2, 1), 2, 2), g, x)[0],
     rtol=1e-5, atol=1e-6)
case("upsampling3d_bp", "upsampling3d_bp",
     (rng.normal(size=(1, 2, 2, 2, 3)).astype(F32),
      rng.normal(size=(1, 4, 4, 4, 3)).astype(F32)), {"scale": 2},
     lambda x, g: _tape_g(
         lambda t: tf.repeat(tf.repeat(tf.repeat(t, 2, 1), 2, 2), 2, 3),
         g, x)[0],
     rtol=1e-5, atol=1e-6)
case("softmax_bp", "softmax_bp", (xsm, gsm), {},
     lambda x, g: _tape_g(tf.nn.softmax, g, x)[0],
     rtol=1e-5, atol=1e-6)
case("log_softmax_bp", "log_softmax_bp", (xsm, gsm), {},
     lambda x, g: _tape_g(tf.nn.log_softmax, g, x)[0],
     rtol=1e-5, atol=1e-6)
case("tanh_bp", "tanh_bp", (x34, x34 * 0.5), {},
     lambda x, g: _tape_g(tf.tanh, g, x)[0], rtol=1e-5, atol=1e-6)
case("sigmoid_bp", "sigmoid_bp", (x34, x34 * 0.5), {},
     lambda x, g: _tape_g(tf.sigmoid, g, x)[0], rtol=1e-5, atol=1e-6)
case("prelu_bp", "prelu_bp",
     (x34, np.array([0.1, 0.2, 0.3, 0.4], F32), x34 * 0.5), {},
     lambda x, a, g: _tape_g(
         lambda t, al: tf.maximum(t, 0.0) + al * tf.minimum(t, 0.0),
         g, x, a),
     out=(0, 1), rtol=1e-5, atol=1e-6)
case("im2col_bp", "im2col_bp",
     (rng.normal(size=(1, 5, 6, 3)).astype(F32),
      rng.normal(size=(1, 4, 2, 18)).astype(F32)),
     {"kernel": (2, 3), "strides": (1, 2), "padding": "VALID"},
     lambda x, g: _tape_g(
         lambda t: (lambda p: tf.reshape(tf.transpose(tf.reshape(
             p, tf.concat([tf.shape(p)[:3], [2, 3, 3]], 0)),
             [0, 1, 2, 5, 3, 4]), tf.shape(p)))(
             tf.image.extract_patches(t, [1, 2, 3, 1], [1, 1, 2, 1],
                                      [1, 1, 1, 1], "VALID")), g, x)[0],
     rtol=1e-5, atol=1e-6)
# ---- recurrent cells/layers vs tf.keras with explicitly mapped weights ----
# Ours: fused w (input+hidden, 4H), gate order i,f,g,o == keras i,f,c,o;
# keras folds forget bias into the bias vector, so forget_bias=0 aligns.
# GRU: keras kernel order is z,r,h (reset_after=False); ours is r,z + w_h.
_RH, _RI, _RB, _RT = 5, 3, 2, 4
_rw = (rng.normal(size=(_RI + _RH, 4 * _RH)) * 0.4).astype(F32)
_rb = (rng.normal(size=(4 * _RH,)) * 0.1).astype(F32)
_rx = rng.normal(size=(_RB, _RI)).astype(F32)
_rh0 = rng.normal(size=(_RB, _RH)).astype(F32)
_rc0 = rng.normal(size=(_RB, _RH)).astype(F32)
_rxs = rng.normal(size=(_RB, _RT, _RI)).astype(F32)
_rwrz = (rng.normal(size=(_RI + _RH, 2 * _RH)) * 0.4).astype(F32)
_rwh = (rng.normal(size=(_RI + _RH, _RH)) * 0.4).astype(F32)
_rbrz = (rng.normal(size=(2 * _RH,)) * 0.1).astype(F32)
_rbh = (rng.normal(size=(_RH,)) * 0.1).astype(F32)


def _keras_lstm_cell_twin(x, h, c, w, b):
    cell = tf.keras.layers.LSTMCell(_RH)
    cell.build((None, _RI))
    cell.set_weights([w[:_RI], w[_RI:], b])
    out, st = cell(tf.constant(x), [tf.constant(h), tf.constant(c)])
    return [np.asarray(out), np.asarray(st[1])]


def _gru_keras_weights(wrz, wh, brz, bh):
    kern = np.concatenate([wrz[:_RI, _RH:], wrz[:_RI, :_RH], wh[:_RI]], 1)
    rec = np.concatenate([wrz[_RI:, _RH:], wrz[_RI:, :_RH], wh[_RI:]], 1)
    bias = np.concatenate([brz[_RH:], brz[:_RH], bh])
    return kern, rec, bias


def _keras_gru_cell_twin(x, h, wrz, wh, brz, bh):
    kern, rec, bias = _gru_keras_weights(wrz, wh, brz, bh)
    cell = tf.keras.layers.GRUCell(_RH, reset_after=False)
    cell.build((None, _RI))
    cell.set_weights([kern, rec, bias])
    out, _st = cell(tf.constant(x), [tf.constant(h)])
    return np.asarray(out)


def _keras_lstm_layer_twin(x, h, c, w, b):
    lay = tf.keras.layers.LSTM(_RH, return_sequences=True)
    lay.build((None, None, _RI))
    lay.set_weights([w[:_RI], w[_RI:], b])
    return np.asarray(lay(tf.constant(x),
                          initial_state=[tf.constant(h), tf.constant(c)]))


def _keras_gru_layer_twin(x, h, wrz, wh, brz, bh):
    kern, rec, bias = _gru_keras_weights(wrz, wh, brz, bh)
    lay = tf.keras.layers.GRU(_RH, reset_after=False, return_sequences=True)
    lay.build((None, None, _RI))
    lay.set_weights([kern, rec, bias])
    return np.asarray(lay(tf.constant(x), initial_state=tf.constant(h)))


case("lstm_cell_keras", "lstm_cell", (_rx, _rh0, _rc0, _rw, _rb),
     {"forget_bias": 0.0}, _keras_lstm_cell_twin, out=(0, 1),
     rtol=1e-5, atol=1e-5)
case("gru_cell_keras", "gru_cell",
     (_rx, _rh0, _rwrz, _rwh, _rbrz, _rbh), {}, _keras_gru_cell_twin,
     rtol=1e-5, atol=1e-5)
case("lstm_layer_keras", "lstm_layer", (_rxs, _rh0, _rc0, _rw, _rb),
     {"forget_bias": 0.0}, _keras_lstm_layer_twin, out=0,
     rtol=1e-4, atol=1e-5)
# lstm_block's TF-style forget_bias default (+1.0 on the f gate) must equal
# keras with the +1 folded into the f-block of the bias vector
case("lstm_block_keras", "lstm_block", (_rxs, _rh0, _rc0, _rw, _rb), {},
     lambda x, h, c, w, b: _keras_lstm_layer_twin(
         x, h, c, w, np.concatenate(
             [b[:_RH], b[_RH:2 * _RH] + 1.0, b[2 * _RH:]]).astype(F32)),
     out=0, rtol=1e-4, atol=1e-5)
case("gru_layer_keras", "gru_layer",
     (_rxs, _rh0, _rwrz, _rwh, _rbrz, _rbh), {}, _keras_gru_layer_twin,
     out=0, rtol=1e-4, atol=1e-5)
# ---- round-5 final tranche: adjoints, no-op edges, infra ops --------------
def _im2col_adjoint_twin(p):
    """Tape-adjoint of the (C,KH,KW)-reordered extract_patches: the ground
    truth col2im must reproduce (caught a channel-ordering bug in col2im)."""
    t = tf.constant(np.zeros((1, 5, 6, 3), F32))
    with tf.GradientTape() as tp:
        tp.watch(t)
        q = tf.image.extract_patches(t, [1, 2, 3, 1], [1, 1, 2, 1],
                                     [1, 1, 1, 1], "VALID")
        q = tf.reshape(tf.transpose(tf.reshape(q, (1, 4, 2, 2, 3, 3)),
                                    [0, 1, 2, 5, 3, 4]), (1, 4, 2, 18))
    return tp.gradient(q, t, output_gradients=tf.constant(p)).numpy()


case("col2im_adjoint", "col2im",
     (rng.normal(size=(1, 4, 2, 18)).astype(F32), (2, 3), (5, 6)),
     {"strides": (1, 2), "padding": "VALID"},
     lambda p, k, hw: _im2col_adjoint_twin(p), rtol=1e-6, atol=1e-7)
_dkey = np.asarray(jax.random.PRNGKey(0))
case("dropout_rate0_identity", "dropout",
     (x34, _dkey), {"rate": 0.0}, lambda x, k: x)
case("dropout_inverted_p1_identity", "dropout_inverted",
     (x34, _dkey), {"p": 1.0}, lambda x, k: x)
case("alpha_dropout_p0_identity", "alpha_dropout",
     (x34,), {"p": 0.0}, lambda x: x)
case("broadcastgradientargs", "broadcastgradientargs",
     (np.array([3, 1, 4], I32), np.array([3, 4], I32)), {},
     lambda a, b: [np.asarray(tf.raw_ops.BroadcastGradientArgs(
         s0=tf.constant(a), s1=tf.constant(b)).r0),
         np.asarray(tf.raw_ops.BroadcastGradientArgs(
             s0=tf.constant(a), s1=tf.constant(b)).r1)],
     out=(0, 1), dtype_strict=False)
case("compat_sparse_to_dense", "compat_sparse_to_dense",
     (np.array([[0, 1], [2, 0]], np.int64), np.array([3, 3], np.int64),
      np.array([5.0, 7.0], F32)), {"default": -1.0},
     lambda i, s, v: _t(tf.compat.v1.sparse_to_dense, i, s, v, -1.0))
case("match_condition_transform", "match_condition_transform",
     (np.array([1., -2., 0., 3.], F32),), {"condition": "gte", "value": 0.0},
     lambda x: (x >= 0.0))


# ---- updater ops vs optax / torch.optim -----------------------------------
# Each registry updater maps (grad, state...) -> (update, new state...).
# Anchors chosen where the eps placement matches: optax for adam/nadam/
# nesterovs (trace isomorphism v = -lr*trace), torch.optim for rmsprop/
# adagrad/adadelta/amsgrad (eps outside the sqrt, like nd4j). Adamax gets
# an explicit-formula twin: torch puts eps inside the max (|g|+eps) where
# nd4j adds it to the denominator (u+eps) — equal at these magnitudes but
# not in general, so torch is not a safe anchor there.
_ug = rng.normal(size=(4,)).astype(F32)
_um = rng.normal(size=(4,)).astype(F32) * 0.1
_uv = np.abs(rng.normal(size=(4,))).astype(F32) * 0.1
_uv2 = np.abs(rng.normal(size=(4,))).astype(F32) * 0.1


def _torch_step(optcls, state, kw, g):
    torch = _torch()
    p = torch.zeros(4, requires_grad=True)
    opt = optcls([p], **kw)
    for k, v in state.items():
        opt.state[p][k] = torch.tensor(v)
    p.grad = torch.tensor(g)
    before = p.detach().clone()
    opt.step()
    return (before - p.detach()).numpy()


def _optax_adam_twin(nesterov):
    def twin(g, m, v):
        import optax
        tx = optax.scale_by_adam(0.9, 0.999, 1e-8, nesterov=nesterov)
        st = optax.ScaleByAdamState(count=jnp.asarray(3),
                                    mu=jnp.asarray(m), nu=jnp.asarray(v))
        u, stn = tx.update(jnp.asarray(g), st)
        return [0.01 * np.asarray(u), np.asarray(stn.mu),
                np.asarray(stn.nu)]
    return twin


case("sgd_updater", "sgd_updater", (_ug,), {"lr": 0.05},
     lambda g: (0.05 * g).astype(F32))
case("adam_updater_optax", "adam_updater", (_ug, _um, _uv),
     {"lr": 0.01, "iteration": 3}, _optax_adam_twin(False),
     out=(0, 1, 2), rtol=1e-5, atol=1e-6)
case("nadam_updater_optax", "nadam_updater", (_ug, _um, _uv),
     {"lr": 0.01, "iteration": 3}, _optax_adam_twin(True),
     out=(0, 1, 2), rtol=1e-5, atol=1e-6)


def _nesterovs_twin(g, v):
    import optax
    tx = optax.trace(decay=0.9, nesterov=True)
    st = optax.TraceState(trace=jnp.asarray(-v / 0.01))
    u, stn = tx.update(jnp.asarray(g), st)
    return [0.01 * np.asarray(u), -0.01 * np.asarray(stn.trace)]


case("nesterovs_updater_optax", "nesterovs_updater",
     (_ug, _um), {"lr": 0.01, "momentum": 0.9}, _nesterovs_twin,
     out=(0, 1), rtol=1e-5, atol=1e-6)
case("rms_prop_updater_torch", "rms_prop_updater", (_ug, _uv),
     {"lr": 0.01, "decay": 0.95},
     lambda g, v: _torch_step(
         _torch().optim.RMSprop,
         {"step": np.float32(1.0), "square_avg": v},
         dict(lr=0.01, alpha=0.95, eps=1e-8), g),
     rtol=1e-5, atol=1e-7)
case("ada_grad_updater_torch", "ada_grad_updater", (_ug, _uv),
     {"lr": 0.01},
     lambda g, h: _torch_step(
         _torch().optim.Adagrad, {"step": np.float32(1.0), "sum": h},
         dict(lr=0.01, eps=1e-8), g),
     rtol=1e-5, atol=1e-7)
case("ada_delta_updater_torch", "ada_delta_updater",
     (_ug, _uv, _uv2), {"rho": 0.95},
     lambda g, msg, msdx: _torch_step(
         _torch().optim.Adadelta,
         {"step": np.float32(1.0), "square_avg": msg, "acc_delta": msdx},
         dict(lr=1.0, rho=0.95, eps=1e-6), g),
     out=0, rtol=1e-5, atol=1e-6)
case("ams_grad_updater_torch", "ams_grad_updater",
     (_ug, _um, _uv, (_uv * 1.5).astype(F32)),
     {"lr": 0.01, "iteration": 3},
     lambda g, m, v, vh: _torch_step(
         _torch().optim.Adam,
         {"step": np.float32(3.0), "exp_avg": m, "exp_avg_sq": v,
          "max_exp_avg_sq": vh},
         dict(lr=0.01, betas=(0.9, 0.999), eps=1e-8, amsgrad=True), g),
     out=0, rtol=1e-5, atol=1e-7)
def _adamax_ref(g, m, u):
    """nd4j AdaMaxUpdater restated: u = max(b2*u, |g|); update =
    lr*m_new/((1-b1^t)*(u_new+eps)), t=4."""
    m_new = 0.9 * m + 0.1 * g
    u_new = np.maximum(0.999 * u, np.abs(g))
    return [(0.002 * m_new / ((1 - 0.9 ** 4) * (u_new + 1e-8)))
            .astype(F32), m_new.astype(F32), u_new.astype(F32)]


case("ada_max_updater_ref", "ada_max_updater",
     (_ug, _um, _uv), {"lr": 0.002, "iteration": 3}, _adamax_ref,
     out=(0, 1, 2), rtol=1e-5, atol=1e-7)


def _lstm_block_cell_twin(x, h, c, w, b):
    z = np.zeros((_RH,), F32)
    t = tf.raw_ops.LSTMBlockCell(
        x=x, cs_prev=c, h_prev=h, w=w, wci=z, wcf=z, wco=z, b=b,
        forget_bias=1.0, use_peephole=False)
    return [np.asarray(v) for v in (t.i, t.cs, t.f, t.o, t.ci, t.co, t.h)]


# gate order i,c,f,o (TF LSTMBlockCell) — NOT lstm_cell's i,f,g,o
case("lstm_block_cell_tf", "lstm_block_cell",
     (_rx, _rh0, _rc0, _rw, _rb), {"forget_bias": 1.0},
     _lstm_block_cell_twin, out=(0, 1, 2, 3, 4, 5, 6),
     rtol=1e-4, atol=1e-4)
case("self_adjoint_eig_values", "self_adjoint_eig",
     ((lambda a: (a + a.T) / 2)(rng.normal(size=(5, 5)).astype(F32)),), {},
     lambda s: np.linalg.eigvalsh(s).astype(F32), out=0,
     rtol=1e-4, atol=1e-5)
case("dynamic_bidirectional_rnn_keras", "dynamic_bidirectional_rnn",
     (_rxs, _rh0, _rc0, _rw, _rb,
      _rh0 * 0.5, _rc0 * 0.5, (_rw * 0.8).astype(F32),
      (_rb * 0.8).astype(F32)),
     {"cell": "lstm", "forget_bias": 0.0},
     lambda x, hf, cf, wf, bf, hb, cb, wb, bb: [
         _keras_lstm_layer_twin(x, hf, cf, wf, bf),
         _keras_lstm_layer_twin(x[:, ::-1], hb, cb, wb, bb)[:, ::-1]],
     out=(0, 1), rtol=1e-4, atol=1e-5)


# ---- ONNX recurrent ops vs torch.nn with mapped weights -------------------
# ONNX gate orders: LSTM i,o,f,c / GRU z,r,h; torch: LSTM i,f,g,o / GRU
# r,z,n (torch GRU == linear_before_reset=1). Weights are drawn as ONNX-
# layout case args; twins load the inverse-reordered blocks into torch.
_OT, _OB, _OI, _OH = 4, 2, 3, 5
_ox = rng.normal(size=(_OT, _OB, _OI)).astype(F32)
_olW = (rng.normal(size=(1, 4 * _OH, _OI)) * 0.4).astype(F32)
_olR = (rng.normal(size=(1, 4 * _OH, _OH)) * 0.4).astype(F32)
_olB = (rng.normal(size=(1, 8 * _OH)) * 0.1).astype(F32)
_ogW = (rng.normal(size=(1, 3 * _OH, _OI)) * 0.4).astype(F32)
_ogR = (rng.normal(size=(1, 3 * _OH, _OH)) * 0.4).astype(F32)
_ogB = (rng.normal(size=(1, 6 * _OH)) * 0.1).astype(F32)
_orW = (rng.normal(size=(1, _OH, _OI)) * 0.4).astype(F32)
_orR = (rng.normal(size=(1, _OH, _OH)) * 0.4).astype(F32)
_orB = (rng.normal(size=(1, 2 * _OH)) * 0.1).astype(F32)


def _onnx2torch_lstm(a):
    i, o, f, c = np.split(a, 4, 0)
    return np.concatenate([i, f, c, o], 0)


def _onnx2torch_gru(a):
    z, r, h = np.split(a, 3, 0)
    return np.concatenate([r, z, h], 0)


def _torch_lstm_twin(x, w, r, b):
    torch = _torch()
    m = torch.nn.LSTM(_OI, _OH, bias=True)
    with torch.no_grad():
        m.weight_ih_l0.copy_(torch.tensor(_onnx2torch_lstm(w[0])))
        m.weight_hh_l0.copy_(torch.tensor(_onnx2torch_lstm(r[0])))
        m.bias_ih_l0.copy_(torch.tensor(
            _onnx2torch_lstm(b[0, :4 * _OH])))
        m.bias_hh_l0.copy_(torch.tensor(
            _onnx2torch_lstm(b[0, 4 * _OH:])))
        y, _ = m(torch.tensor(x))
    return y.numpy()[:, None]                    # (T,B,H) -> (T,D=1,B,H)


def _torch_gru_twin(x, w, r, b):
    torch = _torch()
    m = torch.nn.GRU(_OI, _OH, bias=True)
    with torch.no_grad():
        m.weight_ih_l0.copy_(torch.tensor(_onnx2torch_gru(w[0])))
        m.weight_hh_l0.copy_(torch.tensor(_onnx2torch_gru(r[0])))
        m.bias_ih_l0.copy_(torch.tensor(_onnx2torch_gru(b[0, :3 * _OH])))
        m.bias_hh_l0.copy_(torch.tensor(_onnx2torch_gru(b[0, 3 * _OH:])))
        y, _ = m(torch.tensor(x))
    return y.numpy()[:, None]


def _torch_rnn_twin(x, w, r, b):
    torch = _torch()
    m = torch.nn.RNN(_OI, _OH, bias=True, nonlinearity="tanh")
    with torch.no_grad():
        m.weight_ih_l0.copy_(torch.tensor(w[0]))
        m.weight_hh_l0.copy_(torch.tensor(r[0]))
        m.bias_ih_l0.copy_(torch.tensor(b[0, :_OH]))
        m.bias_hh_l0.copy_(torch.tensor(b[0, _OH:]))
        y, _ = m(torch.tensor(x))
    return y.numpy()[:, None]


def _torch_bilstm_twin(x, w, r, b):
    torch = _torch()
    m = torch.nn.LSTM(_OI, _OH, bias=True, bidirectional=True)
    with torch.no_grad():
        for di, sfx in ((0, ""), (1, "_reverse")):
            getattr(m, "weight_ih_l0" + sfx).copy_(
                torch.tensor(_onnx2torch_lstm(w[di])))
            getattr(m, "weight_hh_l0" + sfx).copy_(
                torch.tensor(_onnx2torch_lstm(r[di])))
            getattr(m, "bias_ih_l0" + sfx).copy_(
                torch.tensor(_onnx2torch_lstm(b[di, :4 * _OH])))
            getattr(m, "bias_hh_l0" + sfx).copy_(
                torch.tensor(_onnx2torch_lstm(b[di, 4 * _OH:])))
        y, _ = m(torch.tensor(x))
    return (y.numpy().reshape(_OT, _OB, 2, _OH)
            .transpose(0, 2, 1, 3))              # (T,B,2H) -> (T,D,B,H)


case("static_rnn_lstm", "static_rnn", (_rxs, _rh0, _rc0, _rw, _rb),
     {"cell": "lstm", "forget_bias": 0.0},
     _keras_lstm_layer_twin, out=0, rtol=1e-4, atol=1e-5)


def _sru_ref(x, c0, w, b):
    """SRU recurrence restated independently in numpy (Lei et al. 2017,
    eq. 3-7 with highway connection on the raw input)."""
    n, t, d = x.shape
    proj = x.astype(np.float64) @ w.astype(np.float64)
    xt_, f_, r_ = np.split(proj, 3, -1)
    bf, br = np.split(b.astype(np.float64), 2)
    f = 1 / (1 + np.exp(-(f_ + bf)))
    r = 1 / (1 + np.exp(-(r_ + br)))
    c = c0.astype(np.float64)
    hs = []
    for k in range(t):
        c = f[:, k] * c + (1 - f[:, k]) * xt_[:, k]
        hs.append(r[:, k] * np.tanh(c) + (1 - r[:, k]) * x[:, k])
    return [np.stack(hs, 1).astype(F32), c.astype(F32)]


_sx = rng.normal(size=(2, 4, 5)).astype(F32)
_sc0 = rng.normal(size=(2, 5)).astype(F32)
_sw = (rng.normal(size=(5, 15)) * 0.4).astype(F32)
_sb = (rng.normal(size=(10,)) * 0.1).astype(F32)
case("sru", "sru", (_sx, _sc0, _sw, _sb), {}, _sru_ref,
     out=(0, 1), rtol=1e-5, atol=1e-5)
case("sru_cell", "sru_cell", (_sx[:, 0], _sc0, _sw, _sb), {},
     lambda x, c, w, b: (lambda hs, cn: [hs[:, 0], cn])(
         *_sru_ref(x[:, None], c, w, b)),
     out=(0, 1), rtol=1e-5, atol=1e-5)
case("onnx_lstm_torch", "onnx_lstm", (_ox, _olW, _olR, _olB), {},
     _torch_lstm_twin, out=0, rtol=1e-5, atol=1e-5)
case("onnx_gru_torch", "onnx_gru", (_ox, _ogW, _ogR, _ogB),
     {"linear_before_reset": 1}, _torch_gru_twin, out=0,
     rtol=1e-5, atol=1e-5)
case("onnx_rnn_torch", "onnx_rnn", (_ox, _orW, _orR, _orB), {},
     _torch_rnn_twin, out=0, rtol=1e-5, atol=1e-5)
_olW2 = (rng.normal(size=(1, 4 * _OH, _OI)) * 0.4).astype(F32)
_olR2 = (rng.normal(size=(1, 4 * _OH, _OH)) * 0.4).astype(F32)
_olB2 = (rng.normal(size=(1, 8 * _OH)) * 0.1).astype(F32)
case("onnx_lstm_bidir_torch", "onnx_lstm",
     (_ox, np.concatenate([_olW, _olW2]),
      np.concatenate([_olR, _olR2]),
      np.concatenate([_olB, _olB2])),
     {"direction": "bidirectional"}, _torch_bilstm_twin, out=0,
     rtol=1e-5, atol=1e-5)
# ---- registry tail: conv variants, NCHW twins, legacy activations ---------
case("deconv2d_tf_kernel", "deconv2d",
     (rng.normal(size=(1, 4, 4, 5)).astype(F32),
      rng.normal(size=(3, 3, 2, 5)).astype(F32) * 0.3),
     {"strides": (2, 2), "padding": "SAME", "transpose_kernel": True},
     lambda x, w: _t(lambda a, f: tf.nn.conv2d_transpose(
         a, f, [1, 8, 8, 2], [1, 2, 2, 1], "SAME"), x, w),
     rtol=1e-4, atol=1e-5)
case("pointwise_conv2d", "pointwise_conv2d",
     (img, rng.normal(size=(1, 1, 3, 6)).astype(F32)), {},
     lambda x, w: _t(tf.nn.conv2d, x, w, [1, 1, 1, 1], "VALID"),
     rtol=1e-4, atol=1e-5)
case("sconv2d", "sconv2d",
     (img, dker, rng.normal(size=(1, 1, 6, 4)).astype(F32) * 0.3),
     {"strides": (1, 1), "padding": "SAME"},
     lambda x, dw, pw: _t(tf.nn.separable_conv2d, x, dw, pw,
                          [1, 1, 1, 1], "SAME"),
     rtol=1e-4, atol=1e-4)
case("conv2d_nchw", "conv2d_nchw",
     (rng.normal(size=(1, 3, 5, 5)).astype(F32),
      rng.normal(size=(4, 3, 3, 3)).astype(F32) * 0.3),
     {"strides": (1, 1), "padding": ((1, 1), (1, 1))},
     lambda x, w: _t(lambda a, f: tf.transpose(tf.nn.conv2d(
         tf.transpose(a, [0, 2, 3, 1]), tf.transpose(f, [2, 3, 1, 0]),
         [1, 1, 1, 1], "SAME"), [0, 3, 1, 2]), x, w),
     rtol=1e-4, atol=1e-5)
case("batchnorm_nchw", "batchnorm_nchw",
     (rng.normal(size=(2, 4, 3, 3)).astype(F32), xr4 * 0.5 + 1.0,
      xr4 - 0.3, xr4, np.abs(xr4) + 0.2), {"epsilon": 1e-3},
     lambda x, s, o, m, v: _t(lambda t: tf.transpose(
         tf.nn.batch_normalization(tf.transpose(t, [0, 2, 3, 1]),
                                   m, v, o, s, 1e-3), [0, 3, 1, 2]), x),
     rtol=1e-4, atol=1e-5)
case("global_avgpool_nchw", "global_avgpool_nchw",
     (rng.normal(size=(2, 3, 4, 5)).astype(F32),), {},
     lambda x: x.mean((2, 3), keepdims=True))
case("global_maxpool_nchw", "global_maxpool_nchw",
     (rng.normal(size=(2, 3, 4, 5)).astype(F32),), {},
     lambda x: x.max((2, 3), keepdims=True))
case("rationaltanh", "rationaltanh", (x34,), {},
     lambda x: (1.7159 * np.tanh(2.0 * x / 3.0)).astype(F32),
     rtol=1e-5, atol=1e-6)
case("rationaltanh_derivative", "rationaltanh_derivative", (x34,), {},
     lambda x: _tape(lambda t: 1.7159 * tf.tanh(2.0 * t / 3.0), x),
     rtol=1e-4, atol=1e-5)
case("rectifiedtanh", "rectifiedtanh",
     (np.array([-1.5, -0.2, 0.4, 2.0], F32),), {},
     lambda x: np.maximum(0.0, np.tanh(x)).astype(F32))
case("rectifiedtanh_derivative", "rectifiedtanh_derivative",
     (np.array([-1.5, -0.2, 0.4, 2.0], F32),), {},
     lambda x: _tape(lambda t: tf.nn.relu(tf.tanh(t)), x),
     rtol=1e-5, atol=1e-6)
case("cosine_distance_ax", "cosine_distance", (x34, x34 * 0.5 + 0.1), {},
     lambda a, b: (1.0 - np.sum(a * b, -1)
                   / (np.linalg.norm(a, axis=-1)
                      * np.linalg.norm(b, axis=-1))).astype(F32),
     rtol=1e-5, atol=1e-6)
case("cosinesim_full", "cosinesim", (x34, x34 * 2.0), {},
     lambda a, b: np.float32(np.sum(a * b)
                             / (np.linalg.norm(a) * np.linalg.norm(b))),
     rtol=1e-5, atol=1e-6)
case("hamming_distance_ext", "hamming_distance",
     (np.array([1., 2., 3.], F32), np.array([1., 0., 3.], F32)), {},
     lambda a, b: np.int64(1), dtype_strict=False)
case("jaccard_distance_ax", "jaccard_distance",
     (np.abs(x34) + 0.1, np.abs(x34[::-1]) + 0.1), {},
     lambda a, b: (1.0 - np.minimum(a, b).sum(-1)
                   / np.maximum(a, b).sum(-1)).astype(F32),
     rtol=1e-5, atol=1e-6)
case("flatten_2d", "flatten_2d",
     (rng.normal(size=(2, 3, 4)).astype(F32),), {"axis": 1},
     lambda x: x.reshape(2, 12))
case("logdet_pd", "logdet",
     (np.array([[4., 1.], [1., 3.]], F32),), {},
     lambda x: np.linalg.slogdet(x)[1].astype(F32),
     rtol=1e-5, atol=1e-6)
_pdm = np.array([[4., 1.], [1., 3.]], F32)
case("cholesky_solve", "cholesky_solve",
     (np.linalg.cholesky(_pdm).astype(F32),
      np.array([[1.], [2.]], F32)), {},
     lambda L, b: np.linalg.solve(L @ L.T, b).astype(F32),
     rtol=1e-4, atol=1e-5)
case("log_entropy", "log_entropy", (np.array([0.2, 0.3, 0.5], F32),), {},
     lambda p: np.log(-(p * np.log(p)).sum()).astype(F32),
     rtol=1e-5, atol=1e-6)
case("logentropy_legacy", "logentropy", (np.array([0.2, 0.3, 0.5], F32),),
     {}, lambda p: np.log(-(p * np.log(p)).sum()).astype(F32),
     rtol=1e-5, atol=1e-6)
case("compare_and_set", "compare_and_set",
     (np.array([1.0, 2.0, 1.0], F32), 1.0, 9.0), {"eps": 1e-6},
     lambda x, c, s: np.where(np.abs(x - c) < 1e-6,
                              np.float32(s), x).astype(F32))
case("grs_to_rgb", "grs_to_rgb",
     (rng.normal(size=(2, 3, 3, 1)).astype(F32),), {},
     lambda x: np.broadcast_to(x, x.shape[:-1] + (3,)))
case("static_bidirectional_rnn", "static_bidirectional_rnn",
     (_rxs, _rh0, _rc0, _rw, _rb, _rh0 * 0.5, _rc0 * 0.5,
      (_rw * 0.8).astype(F32), (_rb * 0.8).astype(F32)),
     {"cell": "lstm", "forget_bias": 0.0},
     lambda x, hf, cf, wf, bf, hb, cb, wb, bb: np.concatenate([
         _keras_lstm_layer_twin(x, hf, cf, wf, bf),
         _keras_lstm_layer_twin(x[:, ::-1], hb, cb, wb, bb)[:, ::-1]], -1),
     out=0, rtol=1e-4, atol=1e-5)
case("sru_bi", "sru_bi",
     (_sx, _sc0, _sw, _sb, _sc0 * 0.5, (_sw * 0.8).astype(F32),
      (_sb * 0.8).astype(F32)), {},
     lambda x, cf, wf, bf, cb, wb, bb: np.concatenate([
         _sru_ref(x, cf, wf, bf)[0],
         _sru_ref(x[:, ::-1].copy(), cb, wb, bb)[0][:, ::-1]], -1),
     out=0, rtol=1e-5, atol=1e-5)
case("dot_product_attention", "dot_product_attention",
     (rng.normal(size=(2, 2, 4, 8)).astype(F32),
      rng.normal(size=(2, 2, 4, 8)).astype(F32),
      rng.normal(size=(2, 2, 4, 8)).astype(F32)), {"scaled": True},
     lambda q, k, v: (lambda s: (np.exp(s - s.max(-1, keepdims=True))
                                 / np.exp(s - s.max(-1, keepdims=True))
                                 .sum(-1, keepdims=True)) @ v)
     (np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(8.0)).astype(F32),
     rtol=1e-4, atol=1e-5)



case("gelu_derivative", "gelu_derivative", (x34,), {},
     lambda x: _tape(tf.nn.gelu, x, approximate=True),
     rtol=1e-4, atol=1e-5)
case("leakyrelu_derivative", "leakyrelu_derivative",
     (np.array([-2.5, -0.7, 0.3, 1.8], F32),), {},
     lambda x: _tape(tf.nn.leaky_relu, x, alpha=0.01))
case("hardsigmoid_derivative", "hardsigmoid_derivative",
     (np.array([-3.0, -1.7, 0.0, 1.7, 3.0], F32),), {},
     lambda x: np.where(np.abs(x) < 2.5, np.float32(0.2),
                        np.float32(0.0)))


@pytest.mark.parametrize(
    "spec", CASES, ids=[c[0] for c in CASES])
def test_op_matches_twin(spec):
    id_, op, args, attrs, twin, rtol, atol, out, dtype_strict = spec
    # This jax build's platform default lowers f32 matmuls to bf16 passes
    # (TPU-style); the sweep compares SEMANTICS against f32 twins, so pin
    # true-f32 contractions for the op under test.
    with jax.default_matmul_precision("highest"):
        got = exec_op(op, *[jnp.asarray(a) for a in args], **attrs)
    want = twin(*args)
    gots = list(got) if isinstance(got, (tuple, list)) else [got]
    wants = want if isinstance(want, list) else [want]
    sel = out if isinstance(out, tuple) else (out,)
    if len(gots) == 1:
        sel = (0,)
    for j, k in enumerate(sel):
        g = np.asarray(gots[k])
        w = np.asarray(wants[j] if len(wants) > 1 else wants[0])
        assert g.shape == w.shape, (g.shape, w.shape)
        if dtype_strict:
            assert g.dtype == w.dtype, (g.dtype, w.dtype)
        if np.issubdtype(w.dtype, np.floating) \
                or np.issubdtype(w.dtype, np.complexfloating):
            np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                       equal_nan=True)
        else:
            np.testing.assert_array_equal(g, w)


def test_conformance_sweep_coverage_gate():
    """The sweep must keep exercising a broad slice of the registry against
    ecosystem twins — shrinking it is a regression. Counts DISTINCT registry
    ops (the r3 verdict's ask: ops-vs-twin, not import rules)."""
    reg = set(registry_names())
    swept = {c[1] for c in CASES}
    missing = swept - reg
    assert not missing, f"cases name unregistered ops: {sorted(missing)}"
    assert len(swept) >= 470, (
        f"conformance sweep covers {len(swept)} registry ops; the gate "
        f"floor is 470 — do not shrink the sweep")


def test_ctc_loss_matches_tf():
    """CTC loss vs tf.nn.ctc_loss on a small lattice (blank=0 both)."""
    rng = np.random.default_rng(3)
    B, T, C, S = 2, 6, 5, 3
    logits = rng.normal(size=(B, T, C)).astype(F32)
    log_probs = np.asarray(jnp.asarray(logits)
                           - jnp.log(jnp.sum(jnp.exp(logits), -1,
                                             keepdims=True)))
    labels = np.array([[1, 2, 3], [2, 2, 4]], np.int32)
    logit_len = np.array([6, 5], np.int32)
    label_len = np.array([3, 2], np.int32)
    ours = exec_op("ctc_loss", log_probs, labels, logit_len, label_len,
                   blank_id=0)
    want = tf.nn.ctc_loss(
        labels=tf.constant(labels), logits=tf.constant(logits),
        label_length=tf.constant(label_len),
        logit_length=tf.constant(logit_len),
        logits_time_major=False, blank_index=0).numpy()
    np.testing.assert_allclose(np.asarray(ours), want, rtol=1e-4,
                               atol=1e-4)


# ---- round-4 tranche 3: linalg decompositions (ambiguity-aware) ---------
class TestLinalgDecompositions:
    """Decompositions are only defined up to sign/order/basis — compare
    RECONSTRUCTIONS and invariants against numpy/TF, not raw factors."""

    A = rng.normal(size=(5, 3)).astype(F32)
    SQ = (rng.normal(size=(4, 4)) * 0.5).astype(F32)
    SPD = (A.T @ A + 3 * np.eye(3)).astype(F32)

    def test_svd_singular_values_and_reconstruction(self):
        u, s, vt = exec_op("svd", jnp.asarray(self.A))
        np.testing.assert_allclose(
            np.asarray(s), np.linalg.svd(self.A, compute_uv=False),
            rtol=1e-4, atol=1e-5)
        rec = np.asarray(u) @ np.diag(np.asarray(s)) @ np.asarray(vt)
        np.testing.assert_allclose(rec, self.A, atol=1e-4)

    def test_qr_reconstruction_and_orthonormality(self):
        q, r = exec_op("qr", jnp.asarray(self.A))
        q, r = np.asarray(q), np.asarray(r)
        np.testing.assert_allclose(q @ r, self.A, atol=1e-4)
        np.testing.assert_allclose(q.T @ q, np.eye(q.shape[1]), atol=1e-4)
        # R upper-triangular
        np.testing.assert_allclose(r, np.triu(r), atol=1e-6)

    def test_eigh_eigenvalues_match_numpy(self):
        w, v = exec_op("self_adjoint_eig", jnp.asarray(self.SPD))
        np.testing.assert_allclose(np.sort(np.asarray(w)),
                                   np.sort(np.linalg.eigvalsh(self.SPD)),
                                   rtol=1e-4, atol=1e-5)
        rec = (np.asarray(v) * np.asarray(w)) @ np.asarray(v).T
        np.testing.assert_allclose(rec, self.SPD, atol=1e-3)

    def test_eig_general_eigenvalues(self):
        w, _v = exec_op("eig", jnp.asarray(self.SQ))
        want = np.linalg.eigvals(self.SQ)
        got = np.asarray(w)
        np.testing.assert_allclose(
            np.sort_complex(got.astype(np.complex64)),
            np.sort_complex(want.astype(np.complex64)), atol=1e-3)

    def test_lu_reconstruction(self):
        p, l, u = exec_op("lu", jnp.asarray(self.SQ))
        rec = np.asarray(p) @ np.asarray(l) @ np.asarray(u)
        np.testing.assert_allclose(rec, self.SQ, atol=1e-4)

    def test_pinv_moore_penrose_conditions(self):
        pv = np.asarray(exec_op("pinv", jnp.asarray(self.A)))
        np.testing.assert_allclose(self.A @ pv @ self.A, self.A, atol=1e-3)
        np.testing.assert_allclose(pv @ self.A @ pv, pv, atol=1e-3)

    def test_lstsq_matches_numpy(self):
        bvec = rng.normal(size=(5, 2)).astype(F32)
        got = np.asarray(exec_op("lstsq", jnp.asarray(self.A),
                                 jnp.asarray(bvec)))
        want = np.linalg.lstsq(self.A, bvec, rcond=None)[0]
        np.testing.assert_allclose(got, want, atol=1e-3)

    def test_matrix_power_and_rank(self):
        got = np.asarray(exec_op("matrix_power", jnp.asarray(self.SQ), 3))
        np.testing.assert_allclose(got,
                                   np.linalg.matrix_power(self.SQ, 3),
                                   rtol=1e-3, atol=1e-4)
        lowrank = np.outer(np.arange(1, 5), np.arange(1, 5)).astype(F32)
        assert int(exec_op("matrix_rank", jnp.asarray(lowrank))) == 1

    def test_sqrtm_squares_back(self):
        r = np.asarray(exec_op("sqrtm", jnp.asarray(self.SPD)))
        np.testing.assert_allclose(r @ r, self.SPD, atol=1e-3)

    def test_monotonic_predicates_match_tf(self):
        inc = np.array([1., 2., 2., 3.], F32)
        strict = np.array([1., 2., 3., 4.], F32)
        dec = np.array([3., 1., 2.], F32)
        for arr in (inc, strict, dec):
            assert bool(exec_op("is_non_decreasing", arr)) \
                == bool(tf.math.is_non_decreasing(arr).numpy())
            assert bool(exec_op("is_strictly_increasing", arr)) \
                == bool(tf.math.is_strictly_increasing(arr).numpy())


# ---- ambiguity-aware linalg decomposition checks (round-5) ----------------
# Direct output comparison is ill-posed (sign/permutation freedom); assert
# the DEFINING property of each factorization instead, plus shape/dtype.

def test_qr_reconstructs():
    a = np.random.default_rng(5).normal(size=(4, 3)).astype(F32)
    q, r = exec_op("qr", jnp.asarray(a))
    q, r = np.asarray(q), np.asarray(r)
    np.testing.assert_allclose(q @ r, a, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(q.T @ q, np.eye(q.shape[1]), atol=1e-5)
    assert np.allclose(r, np.triu(r), atol=1e-6)


def test_svd_reconstructs_and_singular_values_match_tf():
    a = np.random.default_rng(6).normal(size=(4, 3)).astype(F32)
    out = exec_op("svd", jnp.asarray(a))
    s_ours = np.sort(np.asarray(out[1] if isinstance(out, (tuple, list))
                                and np.asarray(out[0]).ndim > 1
                                else out[0]).ravel())[::-1]
    s_tf = np.sort(np.asarray(tf.linalg.svd(a)[0]).ravel())[::-1]
    np.testing.assert_allclose(s_ours, s_tf, rtol=1e-4, atol=1e-5)


def test_lu_reconstructs():
    """Our lu returns explicit (P, L, U) with a = P @ L @ U (scipy
    convention), unit-diagonal L, upper-triangular U."""
    a = np.random.default_rng(7).normal(size=(4, 4)).astype(F32)
    P, L, U = (np.asarray(o) for o in exec_op("lu", jnp.asarray(a)))
    np.testing.assert_allclose(P @ L @ U, a, rtol=1e-4, atol=1e-5)
    assert np.allclose(np.diag(L), 1.0) and np.allclose(L, np.tril(L))
    assert np.allclose(U, np.triu(U), atol=1e-6)
    assert np.allclose(P @ P.T, np.eye(4))       # a permutation


def test_self_adjoint_eig_matches_tf_eigenvalues():
    r = np.random.default_rng(8).normal(size=(4, 4)).astype(F32)
    a = (r + r.T) / 2
    out = exec_op("self_adjoint_eig", jnp.asarray(a))
    outs = [np.asarray(o) for o in (out if isinstance(out, (tuple, list))
                                    else [out])]
    w_ours = np.sort(outs[0].ravel() if outs[0].ndim == 1
                     else outs[1].ravel())
    w_tf = np.sort(np.asarray(tf.linalg.eigh(a)[0]).ravel())
    np.testing.assert_allclose(w_ours, w_tf, rtol=1e-4, atol=1e-4)


def test_pinv_lstsq_matrix_rank_logdet_match_tf():
    g = np.random.default_rng(9)
    a = g.normal(size=(4, 3)).astype(F32)
    np.testing.assert_allclose(np.asarray(exec_op("pinv", jnp.asarray(a))),
                               np.asarray(tf.linalg.pinv(a)),
                               rtol=1e-3, atol=1e-4)
    b = g.normal(size=(4, 2)).astype(F32)
    ours = np.asarray(exec_op("lstsq", jnp.asarray(a), jnp.asarray(b)))
    want = np.asarray(tf.linalg.lstsq(a, b, fast=False))
    np.testing.assert_allclose(ours, want, rtol=1e-3, atol=1e-4)
    assert int(np.asarray(exec_op("matrix_rank", jnp.asarray(a)))) == 3
    pd = a.T @ a + 3 * np.eye(3, dtype=F32)
    np.testing.assert_allclose(
        np.asarray(exec_op("logdet", jnp.asarray(pd))),
        np.asarray(tf.linalg.logdet(pd.astype(np.float64))).astype(F32),
        rtol=1e-4, atol=1e-4)
    sign_ld = exec_op("log_matrix_determinant", jnp.asarray(pd))
    outs = [np.asarray(o) for o in sign_ld]
    np.testing.assert_allclose(
        outs[-1], np.linalg.slogdet(pd)[1].astype(F32),
        rtol=1e-4, atol=1e-4)


def test_sqrtm_and_cholesky_solve():
    g = np.random.default_rng(10)
    r = g.normal(size=(3, 3)).astype(F32)
    pd = r @ r.T + 3 * np.eye(3, dtype=F32)
    s = np.asarray(exec_op("sqrtm", jnp.asarray(pd)))
    np.testing.assert_allclose(s @ s, pd, rtol=1e-3, atol=1e-3)
    chol = np.linalg.cholesky(pd).astype(F32)
    rhs = g.normal(size=(3, 2)).astype(F32)
    ours = np.asarray(exec_op("cholesky_solve", jnp.asarray(chol),
                              jnp.asarray(rhs)))
    want = np.asarray(tf.linalg.cholesky_solve(
        tf.constant(chol), tf.constant(rhs)))
    np.testing.assert_allclose(ours, want, rtol=1e-3, atol=1e-4)


# ---- random-distribution moment checks (round-5: sampling ops can't be
# value-compared; assert distributional moments against the analytic law) --

def _moments(x):
    x = np.asarray(x, np.float64).ravel()
    return x.mean(), x.var()


def test_random_normal_moments():
    x = exec_op("normal", (20000,), mean=1.5, stddev=2.0, seed=7)
    m, v = _moments(x)
    assert abs(m - 1.5) < 0.06 and abs(v - 4.0) < 0.25


def test_random_uniform_moments():
    x = exec_op("uniform", (20000,), minval=-1.0, maxval=3.0, seed=7)
    m, v = _moments(x)
    assert abs(m - 1.0) < 0.06 and abs(v - 16.0 / 12.0) < 0.12
    xa = np.asarray(x)
    assert xa.min() >= -1.0 and xa.max() < 3.0


def test_lognormal_moments():
    x = exec_op("lognormal", (40000,), mean=0.0, stddev=0.5, seed=3)
    m, _ = _moments(x)
    assert abs(m - np.exp(0.125)) < 0.08        # E = exp(mu + s^2/2)


def test_truncatednormal_moments_and_support():
    x = exec_op("truncatednormal", (20000,), mean=0.0, stddev=1.0, seed=5)
    xa = np.asarray(x)
    # TF semantics: resample beyond 2 sigma
    assert np.abs(xa).max() <= 2.0 + 1e-5
    assert abs(xa.mean()) < 0.05
    assert abs(xa.var() - 0.774) < 0.08          # var of N(0,1)|[-2,2]


def test_binomial_and_bernoulli_moments():
    x = np.asarray(exec_op("binomial", (20000,), trials=10, p=0.3, seed=11),
                   np.float64)
    assert abs(x.mean() - 3.0) < 0.1 and abs(x.var() - 2.1) < 0.25
    b = np.asarray(exec_op("bernoulli_sample",
                           np.full((20000,), 0.25, F32), seed=13),
                   np.float64)
    assert abs(b.mean() - 0.25) < 0.03
    assert set(np.unique(b)) <= {0.0, 1.0}


def test_random_gamma_poisson_exponential_moments():
    import jax as _jax
    key = _jax.random.key(0)
    g = np.asarray(exec_op("random_gamma", key, 3.0, shape=(20000,)),
                   np.float64)
    assert abs(g.mean() - 3.0) < 0.15 and abs(g.var() - 3.0) < 0.4
    pz = np.asarray(exec_op("random_poisson", key, 4.0, shape=(20000,)),
                    np.float64)
    assert abs(pz.mean() - 4.0) < 0.15 and abs(pz.var() - 4.0) < 0.45
    e = np.asarray(exec_op("random_exponential", key, 2.0, (20000,)),
                   np.float64)
    assert abs(e.mean() - 0.5) < 0.04 and abs(e.var() - 0.25) < 0.06


def test_random_shuffle_is_permutation():
    import jax as _jax
    x = np.arange(1000, dtype=I32)
    y = np.asarray(exec_op("random_shuffle", _jax.random.key(2), x))
    assert not np.array_equal(y, x)
    assert np.array_equal(np.sort(y), x)


def test_random_categorical_frequencies():
    import jax as _jax
    logits = np.log(np.array([[0.1, 0.2, 0.7]], F32))
    y = np.asarray(exec_op("random_categorical", _jax.random.key(4),
                           logits, 30000)).ravel()
    freq = np.bincount(y, minlength=3) / y.size
    np.testing.assert_allclose(freq, [0.1, 0.2, 0.7], atol=0.02)
