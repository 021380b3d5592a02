"""``nd`` — the array factory, analog of ``org.nd4j.linalg.factory.Nd4j``.

The reference's ``Nd4j`` is a ~7k-line static factory whose backend is chosen
by classpath ServiceLoader (``Nd4jBackend#load``). Here the "backend" is the
jax platform (tpu/cpu), selected by ``JAX_PLATFORMS`` / available devices —
the same user-facing contract: user code never names a backend.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ndarray import dtypes as _dt
from deeplearning4j_tpu.ndarray import random as _rng
from deeplearning4j_tpu.ndarray.ndarray import NDArray, _unwrap

_default_dtype = jnp.dtype(jnp.float32)


def setDefaultDataType(dtype):
    """Ref: Nd4j.setDefaultDataTypes."""
    global _default_dtype
    _default_dtype = jnp.dtype(_dt.resolve(dtype))


def defaultFloatingPointType():
    return _default_dtype


def backend() -> str:
    """The active compute platform (ref: Nd4jBackend discovery)."""
    return jax.default_backend()


def _shape(args) -> tuple:
    if len(args) == 1 and isinstance(args[0], (tuple, list)):
        return tuple(args[0])
    return tuple(int(a) for a in args)


# ------------------------------------------------------------------ creation
def create(data, dtype=None) -> NDArray:
    arr = jnp.asarray(_unwrap(data) if isinstance(data, NDArray) else data)
    if dtype is not None:
        arr = arr.astype(_dt.resolve(dtype))
    elif arr.dtype == jnp.float64 and not jax.config.jax_enable_x64:
        arr = arr.astype(_default_dtype)
    return NDArray(arr)


def array(data, dtype=None) -> NDArray:
    return create(data, dtype)


def zeros(*shape, dtype=None) -> NDArray:
    return NDArray(jnp.zeros(_shape(shape), dtype=_dt.resolve(dtype) or _default_dtype))


def ones(*shape, dtype=None) -> NDArray:
    return NDArray(jnp.ones(_shape(shape), dtype=_dt.resolve(dtype) or _default_dtype))


def full(shape, value, dtype=None) -> NDArray:
    return NDArray(jnp.full(tuple(shape), value, dtype=_dt.resolve(dtype) or _default_dtype))


def valueArrayOf(shape, value, dtype=None) -> NDArray:
    return full(shape, value, dtype)


def zerosLike(a) -> NDArray:
    return NDArray(jnp.zeros_like(_unwrap(a)))


def onesLike(a) -> NDArray:
    return NDArray(jnp.ones_like(_unwrap(a)))


def eye(n, m=None, dtype=None) -> NDArray:
    return NDArray(jnp.eye(n, m, dtype=_dt.resolve(dtype) or _default_dtype))


def arange(*args, dtype=None) -> NDArray:
    return NDArray(jnp.arange(*args, dtype=_dt.resolve(dtype)))


def linspace(start, stop, num, dtype=None) -> NDArray:
    return NDArray(jnp.linspace(start, stop, num, dtype=_dt.resolve(dtype) or _default_dtype))


def scalar(value, dtype=None) -> NDArray:
    return NDArray(jnp.asarray(value, dtype=_dt.resolve(dtype) or (_default_dtype if isinstance(value, float) else None)))


def empty(dtype=None) -> NDArray:
    return NDArray(jnp.zeros((0,), dtype=_dt.resolve(dtype) or _default_dtype))


# ---------------------------------------------------------------------- rng
def rand(*shape, dtype=None, seed: Optional[int] = None) -> NDArray:
    """U[0,1). Ref: Nd4j.rand."""
    key = jax.random.key(seed) if seed is not None else _rng.next_key()
    return NDArray(jax.random.uniform(key, _shape(shape), dtype=_dt.resolve(dtype) or _default_dtype))


def randn(*shape, dtype=None, seed: Optional[int] = None) -> NDArray:
    """N(0,1). Ref: Nd4j.randn."""
    key = jax.random.key(seed) if seed is not None else _rng.next_key()
    return NDArray(jax.random.normal(key, _shape(shape), dtype=_dt.resolve(dtype) or _default_dtype))


def randint(low, high, shape, seed: Optional[int] = None) -> NDArray:
    key = jax.random.key(seed) if seed is not None else _rng.next_key()
    return NDArray(jax.random.randint(key, tuple(shape), low, high))


def shuffle(a, seed: Optional[int] = None) -> NDArray:
    key = jax.random.key(seed) if seed is not None else _rng.next_key()
    return NDArray(jax.random.permutation(key, _unwrap(a), axis=0))


def getRandom() -> _rng.Random:
    return _rng.get_random()


def setSeed(seed: int):
    _rng.set_seed(seed)


# ------------------------------------------------------------------ combine
def concat(dim: int, *arrays) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return NDArray(jnp.concatenate([_unwrap(a) for a in arrays], axis=dim))


def stack(dim: int, *arrays) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return NDArray(jnp.stack([_unwrap(a) for a in arrays], axis=dim))


def vstack(*arrays) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return NDArray(jnp.vstack([_unwrap(a) for a in arrays]))


def hstack(*arrays) -> NDArray:
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return NDArray(jnp.hstack([_unwrap(a) for a in arrays]))


def where(cond, x=None, y=None) -> NDArray:
    if x is None:
        return NDArray(jnp.stack(jnp.where(_unwrap(cond)), axis=-1))
    return NDArray(jnp.where(_unwrap(cond), _unwrap(x), _unwrap(y)))


def pad(a, pad_width, mode="constant", constant_values=0) -> NDArray:
    if mode == "constant":
        return NDArray(jnp.pad(_unwrap(a), pad_width, mode=mode, constant_values=constant_values))
    return NDArray(jnp.pad(_unwrap(a), pad_width, mode=mode))


def gather(a, indices, axis=0) -> NDArray:
    return NDArray(jnp.take(_unwrap(a), _unwrap(indices), axis=axis))


def sort(a, axis=-1, descending=False) -> NDArray:
    out = jnp.sort(_unwrap(a), axis=axis)
    return NDArray(jnp.flip(out, axis=axis) if descending else out)


def diag(a) -> NDArray:
    return NDArray(jnp.diag(_unwrap(a)))


# --------------------------------------------------------------------------
# Nd4j static surface, tranche 2 (ref: org.nd4j.linalg.factory.Nd4j ~7k
# lines of statics — IO, structure, random-distribution, reduction tails)

def readNumpy(path, dtype=None) -> NDArray:
    """ref: Nd4j.readNumpy — .npy file → array. ``dtype`` accepts the
    DL4J-style names every other factory API does ("float" == float32)."""
    arr = np.load(path)
    return NDArray(jnp.asarray(arr if dtype is None
                               else arr.astype(_dt.resolve(dtype))))


def writeNumpy(arr, path) -> None:
    np.save(path, np.asarray(_unwrap(arr)))


createFromNpyFile = readNumpy


def saveBinary(arr, path) -> None:
    """ref: Nd4j.saveBinary — portable single-array binary (npy format)."""
    np.save(path, np.asarray(_unwrap(arr)))


def readBinary(path) -> NDArray:
    return NDArray(jnp.asarray(np.load(path)))


def toFlattened(*arrays) -> NDArray:
    """ref: Nd4j.toFlattened — concat everything as one flat vector."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return NDArray(jnp.concatenate([jnp.ravel(_unwrap(a))
                                    for a in arrays]))


def expandDims(a, axis) -> NDArray:
    return NDArray(jnp.expand_dims(_unwrap(a), axis))


def squeeze(a, axis=None) -> NDArray:
    return NDArray(jnp.squeeze(_unwrap(a), axis))


def tile(a, *reps) -> NDArray:
    reps = reps[0] if len(reps) == 1 and isinstance(reps[0],
                                                    (list, tuple)) else reps
    return NDArray(jnp.tile(_unwrap(a), reps))


def repeat(a, repeats, axis=None) -> NDArray:
    return NDArray(jnp.repeat(_unwrap(a), repeats, axis=axis))


def reverse(a, axis=None) -> NDArray:
    """ref: Nd4j.reverse."""
    return NDArray(jnp.flip(_unwrap(a), axis=axis))


flip = reverse


def roll(a, shift, axis=None) -> NDArray:
    return NDArray(jnp.roll(_unwrap(a), shift, axis=axis))


def triu(a, k=0) -> NDArray:
    return NDArray(jnp.triu(_unwrap(a), k))


def tril(a, k=0) -> NDArray:
    return NDArray(jnp.tril(_unwrap(a), k))


def meshgrid(*xs, indexing="xy"):
    return tuple(NDArray(g) for g in
                 jnp.meshgrid(*[_unwrap(x) for x in xs],
                              indexing=indexing))


def split(a, parts, axis=0):
    return [NDArray(p) for p in jnp.split(_unwrap(a), parts, axis=axis)]


def kron(a, b) -> NDArray:
    return NDArray(jnp.kron(_unwrap(a), _unwrap(b)))


def dot(a, b) -> NDArray:
    return NDArray(jnp.dot(_unwrap(a), _unwrap(b)))


def matmul(a, b) -> NDArray:
    return NDArray(jnp.matmul(_unwrap(a), _unwrap(b)))


def pile(*arrays) -> NDArray:
    """ref: Nd4j.pile — stack along a new leading axis."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return NDArray(jnp.stack([_unwrap(a) for a in arrays], axis=0))


def tear(a, axis=0):
    """ref: Nd4j.tear — unstack along an axis."""
    buf = _unwrap(a)
    return [NDArray(jnp.squeeze(p, axis=axis))
            for p in jnp.split(buf, buf.shape[axis], axis=axis)]


def argMax(a, axis=None) -> NDArray:
    return NDArray(jnp.argmax(_unwrap(a), axis=axis).astype(jnp.int32))


def argMin(a, axis=None) -> NDArray:
    return NDArray(jnp.argmin(_unwrap(a), axis=axis).astype(jnp.int32))


# random-distribution statics (ref: Nd4j.randomBernoulli etc.) — route
# through the stateful RNG facade so setSeed governs reproducibility

def randomBernoulli(p, *shape) -> NDArray:
    return NDArray(jax.random.bernoulli(_rng.next_key(), p, tuple(shape))
                   .astype(jnp.float32))


def randomExponential(lam, *shape) -> NDArray:
    return NDArray(jax.random.exponential(_rng.next_key(), tuple(shape))
                   / lam)


def randomGamma(alpha, *shape) -> NDArray:
    return NDArray(jax.random.gamma(_rng.next_key(), alpha, tuple(shape)))


def randomPoisson(lam, *shape) -> NDArray:
    return NDArray(jax.random.poisson(_rng.next_key(), lam, tuple(shape))
                   .astype(jnp.float32))


def randomBinomial(n, p, *shape) -> NDArray:
    # O(shape) memory — never materialize an (n, *shape) bernoulli tensor
    return NDArray(jax.random.binomial(_rng.next_key(), float(n), p,
                                       tuple(shape)).astype(jnp.float32))


def choice(source, probs, n) -> NDArray:
    src = _unwrap(source)
    idx = jax.random.choice(_rng.next_key(), src.shape[0], (int(n),),
                            p=_unwrap(probs))
    return NDArray(jnp.take(src, idx, axis=0))


# reduction statics (ref: Nd4j.max/min/mean/std/sum/var/norm1/norm2)
def max(a, axis=None) -> NDArray:
    return NDArray(jnp.max(_unwrap(a), axis=axis))


def min(a, axis=None) -> NDArray:
    return NDArray(jnp.min(_unwrap(a), axis=axis))


def sum(a, axis=None) -> NDArray:
    return NDArray(jnp.sum(_unwrap(a), axis=axis))


def mean(a, axis=None) -> NDArray:
    return NDArray(jnp.mean(_unwrap(a), axis=axis))


def std(a, axis=None) -> NDArray:
    return NDArray(jnp.std(_unwrap(a), axis=axis, ddof=1))


def var(a, axis=None) -> NDArray:
    return NDArray(jnp.var(_unwrap(a), axis=axis, ddof=1))


def norm1(a, axis=None) -> NDArray:
    return NDArray(jnp.sum(jnp.abs(_unwrap(a)), axis=axis))


def norm2(a, axis=None) -> NDArray:
    return NDArray(jnp.sqrt(jnp.sum(jnp.square(_unwrap(a)), axis=axis)))


def normmax(a, axis=None) -> NDArray:
    return NDArray(jnp.max(jnp.abs(_unwrap(a)), axis=axis))


def prod(a, axis=None) -> NDArray:
    return NDArray(jnp.prod(_unwrap(a), axis=axis))


def getExecutioner():
    """ref: Nd4j.getExecutioner() — the op-execution facade."""
    from deeplearning4j_tpu.ndarray.executioner import get_executioner
    return get_executioner()


# --------------------------------------------------------------------------
# Nd4j static surface, tranche 3 (ref: org.nd4j.linalg.factory.Nd4j — the
# creation-overload, linalg, accumulation, serialization and env tails)

def createFromArray(*values, dtype=None) -> NDArray:
    """ref: Nd4j.createFromArray(...) — varargs scalars or nested lists."""
    if len(values) == 1 and isinstance(values[0], (list, tuple, np.ndarray)):
        values = values[0]
    return create(np.asarray(values), dtype)


def fromNumpy(arr) -> NDArray:
    """ref: Nd4j.createFromNpyPointer analog — zero-copy numpy ingest."""
    return NDArray(jnp.asarray(arr))


def createUninitialized(*shape, dtype=None) -> NDArray:
    """ref: Nd4j.createUninitialized — XLA has no uninitialized memory;
    zeros (the reference's contract is 'contents undefined', zeros satisfy)."""
    return zeros(*shape, dtype=dtype)


createUninitializedDetached = createUninitialized


def trueScalar(value) -> NDArray:
    """ref: Nd4j.trueScalar (rank-0)."""
    return NDArray(jnp.asarray(value))


def trueVector(values) -> NDArray:
    return NDArray(jnp.asarray(values).reshape(-1))


emptyLike = zerosLike


def rot90(a, k: int = 1) -> NDArray:
    """ref: Nd4j.rot90."""
    return NDArray(jnp.rot90(_unwrap(a), k))


def flipud(a) -> NDArray:
    return NDArray(jnp.flipud(_unwrap(a)))


def fliplr(a) -> NDArray:
    return NDArray(jnp.fliplr(_unwrap(a)))


def gemm(a, b, transpose_a=False, transpose_b=False, alpha=1.0, beta=0.0,
         c=None) -> NDArray:
    """ref: Nd4j.gemm — C = alpha·op(A)·op(B) + beta·C. bf16 operands ride
    the MXU with f32 accumulation."""
    A = _unwrap(a).T if transpose_a else _unwrap(a)
    B = _unwrap(b).T if transpose_b else _unwrap(b)
    prefer = jnp.float32 if A.dtype in (jnp.bfloat16, jnp.float16) else None
    out = alpha * jnp.matmul(A, B, preferred_element_type=prefer)
    if c is not None and beta != 0.0:
        out = out + beta * _unwrap(c)
    if isinstance(c, NDArray):
        return c._write(out.astype(c.dtype))
    return NDArray(out)


def tensorMmul(a, b, axes) -> NDArray:
    """ref: Nd4j.tensorMmul."""
    return NDArray(jnp.tensordot(_unwrap(a), _unwrap(b), axes=axes))


def outer(a, b) -> NDArray:
    return NDArray(jnp.outer(_unwrap(a), _unwrap(b)))


def accumulate(*arrays) -> NDArray:
    """ref: Nd4j.accumulate — elementwise sum of N same-shape arrays."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    out = _unwrap(arrays[0])
    for a in arrays[1:]:
        out = out + _unwrap(a)
    return NDArray(out)


def average(*arrays) -> NDArray:
    """ref: Nd4j.averageAndPropagate family — mean of N arrays."""
    if len(arrays) == 1 and isinstance(arrays[0], (list, tuple)):
        arrays = arrays[0]
    return NDArray(accumulate(list(arrays)).buf() / len(arrays))


averageAndPropagate = average


def appendBias(*vectors) -> NDArray:
    """ref: Nd4j.appendBias — concat column vectors and append a 1.0 bias."""
    if len(vectors) == 1 and isinstance(vectors[0], (list, tuple)):
        vectors = vectors[0]
    flat = jnp.concatenate([jnp.ravel(_unwrap(v)) for v in vectors])
    return NDArray(jnp.concatenate([flat, jnp.ones((1,), flat.dtype)])
                   .reshape(-1, 1))


def bilinearProducts(curr, in_):
    """ref: Nd4j.bilinearProducts — d-vector of x^T·T[d]·y slices."""
    T = _unwrap(curr)          # (d, n, n)
    x = _unwrap(in_).reshape(-1)
    return NDArray(jnp.einsum("dij,i,j->d", T, x, x))


def isMax(a, axis=None) -> NDArray:
    """ref: Nd4j.getExecutioner IsMax op — one-hot of the argmax."""
    buf = _unwrap(a)
    if axis is None:
        flat = buf.ravel()
        return NDArray((jnp.arange(flat.size) == jnp.argmax(flat))
                       .reshape(buf.shape).astype(buf.dtype))
    idx = jnp.argmax(buf, axis=axis, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, buf.shape, axis)
    return NDArray((iota == idx).astype(buf.dtype))


def scatterUpdate(op: str, array, indices, updates, axis=0) -> NDArray:
    """ref: Nd4j.scatterUpdate — in-place indexed update (add/sub/mul/assign)."""
    buf = _unwrap(array)
    idx = jnp.asarray(_unwrap(indices))
    upd = jnp.asarray(_unwrap(updates), buf.dtype)
    at = buf.at[idx] if axis == 0 else buf.at[(slice(None),) * axis + (idx,)]
    out = {"add": at.add, "sub": lambda u: at.add(-u), "mul": at.multiply,
           "assign": at.set}[op](upd)
    if isinstance(array, NDArray):
        return array._write(out)
    return NDArray(out)


def sortRows(a, column: int = 0, ascending=True) -> NDArray:
    """ref: Nd4j.sortRows — reorder rows by one column's values."""
    buf = _unwrap(a)
    order = jnp.argsort(buf[:, column])
    if not ascending:
        order = jnp.flip(order)
    return NDArray(buf[order])


def sortColumns(a, row: int = 0, ascending=True) -> NDArray:
    buf = _unwrap(a)
    order = jnp.argsort(buf[row, :])
    if not ascending:
        order = jnp.flip(order)
    return NDArray(buf[:, order])


def sortWithIndices(a, dim=-1, ascending=True):
    """ref: Nd4j.sortWithIndices — (indices, sorted) pair."""
    buf = _unwrap(a)
    idx = jnp.argsort(buf, axis=dim)
    if not ascending:
        idx = jnp.flip(idx, axis=dim)
    return (NDArray(idx.astype(jnp.int32)),
            NDArray(jnp.take_along_axis(buf, idx, axis=dim)))


def stripOnes(a) -> NDArray:
    """ref: Nd4j.stripOnes — squeeze all size-1 dims."""
    return NDArray(jnp.squeeze(_unwrap(a)))


def clearNans(a) -> NDArray:
    """ref: Nd4j.clearNans — in-place NaN→0."""
    buf = _unwrap(a)
    out = jnp.where(jnp.isnan(buf), jnp.zeros((), buf.dtype), buf)
    if isinstance(a, NDArray):
        return a._write(out)
    return NDArray(out)


def cumsum(a, axis=None) -> NDArray:
    return NDArray(jnp.cumsum(_unwrap(a), axis=axis))


def cumprod(a, axis=None) -> NDArray:
    return NDArray(jnp.cumprod(_unwrap(a), axis=axis))


def exec_(op, *args, **kwargs):
    """ref: Nd4j.exec(Op/CustomOp) — run a registry op eagerly by name."""
    from deeplearning4j_tpu.ops.registry import exec_op
    return exec_op(op, *args, **kwargs)


def dataType():
    """ref: Nd4j.dataType() — the default floating point type."""
    return _default_dtype


setDefaultDataTypes = setDefaultDataType


def sizeOfDataType(dtype=None) -> int:
    """ref: Nd4j.sizeOfDataType — bytes per element."""
    return jnp.dtype(_dt.resolve(dtype) if dtype is not None
                     else _default_dtype).itemsize


def getBackend() -> str:
    return backend()


def getStrides(shape, order="c"):
    """ref: Nd4j.getStrides — row/col-major element strides for a shape."""
    shape = tuple(shape)
    if order == "f":
        out, acc = [], 1
        for s in shape:
            out.append(acc)
            acc *= s
        return tuple(out)
    out, acc = [], 1
    for s in reversed(shape):
        out.append(acc)
        acc *= s
    return tuple(reversed(out))


def checkShapeValues(shape) -> None:
    """ref: Nd4j.checkShapeValues — reject negatives/overflow."""
    for s in shape:
        if int(s) < 0:
            raise ValueError(f"negative dimension in shape {tuple(shape)}")


def toByteArray(arr) -> bytes:
    """ref: Nd4j.toByteArray — portable npy bytes."""
    import io
    bio = io.BytesIO()
    np.save(bio, np.asarray(_unwrap(arr)))
    return bio.getvalue()


def fromByteArray(data: bytes) -> NDArray:
    import io
    return NDArray(jnp.asarray(np.load(io.BytesIO(data))))


def toNpyByteArray(arr) -> bytes:
    return toByteArray(arr)


createNpyFromByteArray = fromByteArray


def writeTxt(arr, path, sep=",") -> None:
    """ref: Nd4j.writeTxt."""
    a = np.asarray(_unwrap(arr))
    header = f"shape={a.shape}"
    rows = a.shape[0] if a.ndim > 1 else 1
    np.savetxt(path, a.reshape(rows, -1), delimiter=sep, header=header)


def readTxt(path, sep=",") -> NDArray:
    """ref: Nd4j.readTxt — reads writeTxt output (shape in header)."""
    with open(path) as f:
        first = f.readline()
    data = np.loadtxt(path, delimiter=sep)
    if first.startswith("# shape="):
        shape = tuple(int(x) for x in
                      first.strip()[len("# shape=("):-1].split(",") if x.strip())
        data = data.reshape(shape)
    return NDArray(jnp.asarray(data))


def write(arr, path) -> None:
    """ref: Nd4j.write(INDArray, DataOutputStream) — binary single array."""
    saveBinary(arr, path)


def read(path) -> NDArray:
    return readBinary(path)


def getAffinityManager():
    """ref: Nd4j.getAffinityManager — device placement facade. XLA/PJRT owns
    placement; exposes the current device list."""
    class _Affinity:
        def getNumberOfDevices(self):
            return len(jax.devices())

        def getDeviceForCurrentThread(self):
            return 0
    return _Affinity()


def getMemoryManager():
    """ref: Nd4j.getMemoryManager — PJRT owns memory; live-buffer stats."""
    class _Mem:
        def getCurrentWorkspace(self):
            return None

        def allocatedMemory(self, device=0):
            try:
                stats = jax.local_devices()[device].memory_stats()
                return int(stats.get("bytes_in_use", 0)) if stats else 0
            except Exception:
                return 0
    return _Mem()


def create_shaped(*args, dtype=None, order="c") -> NDArray:
    """ref: Nd4j.create(int...)/(double[])/(data, shape, order) — the
    creation mega-overload. Dispatch mirrors the reference's: int varargs /
    an int list = shape (Java ``create(int[])`` allocates); a float list,
    nested list, or numpy array = data; data + shape tuple = reshape."""
    if args and isinstance(args[0], (list, np.ndarray)):
        data = np.asarray(args[0])
        if len(args) >= 2 and isinstance(args[1], (tuple, list)):
            shape = tuple(args[1])
            buf = create(data.ravel(), dtype).buf()
            arr = buf.reshape(shape[::-1]).T if order == "f" \
                else buf.reshape(shape)
            return NDArray(arr)
        if data.ndim > 1 or not np.issubdtype(data.dtype, np.integer) \
                or isinstance(args[0], np.ndarray):
            return create(data, dtype)
        # flat python int list = shape, matching Java create(int[])
        return zeros(*data.tolist(), dtype=dtype)
    return zeros(*args, dtype=dtype)


class Nd4j:
    """The reference-spelled static facade: ``Nd4j.zeros(...)`` etc.

    ref: org.nd4j.linalg.factory.Nd4j (~7k-line static factory). Every
    module-level factory function is exposed as a static; the class exists
    so reference code translates 1:1 (``Nd4j.create`` → ``Nd4j.create``).
    Populated at import time from this module's public functions.
    """
    pass


def _populate_nd4j_facade():
    import sys
    mod = sys.modules[__name__]
    skip = {"NDArray", "Nd4j"}
    for name in dir(mod):
        if name.startswith("_") or name in skip:
            continue
        obj = getattr(mod, name)
        if callable(obj) and getattr(obj, "__module__", "").endswith(
                ("factory", "random")):
            setattr(Nd4j, name, staticmethod(obj))
    # reference-spelled aliases
    Nd4j.create = staticmethod(create_shaped)
    Nd4j.createFromData = staticmethod(create)
    Nd4j.exec_ = staticmethod(exec_)
    setattr(Nd4j, "exec", staticmethod(exec_))  # valid since py3 — 1:1 spelling
    Nd4j.getRandomFactory = staticmethod(getRandom)
    Nd4j.defaultFloatingPointType = staticmethod(defaultFloatingPointType)




# --------------------------------------------------------------------------
# BLAS/LAPACK facade (ref: Nd4j.getBlasWrapper() →
# org.nd4j.linalg.factory.BlasWrapper + .lapack()). On TPU these lower to
# XLA's linalg lowerings (QR/SVD/Cholesky run on device); the facade keeps
# the reference's call shape.

class _Lapack:
    """ref: org.nd4j.linalg.api.blas.Lapack."""

    def gesvd(self, a):
        u, s, vt = jnp.linalg.svd(_unwrap(a), full_matrices=False)
        return NDArray(u), NDArray(s), NDArray(vt)

    def potrf(self, a, lower=True):
        c = jnp.linalg.cholesky(_unwrap(a))
        return NDArray(c if lower else c.T)

    def getrf(self, a):
        import jax.scipy.linalg as jsl
        lu, piv = jsl.lu_factor(_unwrap(a))
        return NDArray(lu), NDArray(piv)

    def syev(self, a):
        w, v = jnp.linalg.eigh(_unwrap(a))
        return NDArray(w), NDArray(v)

    def geqrf(self, a):
        q, r = jnp.linalg.qr(_unwrap(a))
        return NDArray(q), NDArray(r)


class _BlasWrapper:
    """ref: org.nd4j.linalg.factory.BlasWrapper (level1/2/3 + lapack)."""

    def lapack(self):
        return _Lapack()

    def dot(self, x, y):
        return float(jnp.vdot(_unwrap(x), _unwrap(y)))

    def nrm2(self, x):
        return float(jnp.linalg.norm(jnp.ravel(_unwrap(x))))

    def asum(self, x):
        return float(jnp.sum(jnp.abs(_unwrap(x))))

    def iamax(self, x):
        return int(jnp.argmax(jnp.abs(jnp.ravel(_unwrap(x)))))

    def scal(self, alpha, x):
        if isinstance(x, NDArray):
            return x._write(alpha * x.buf())
        return NDArray(alpha * _unwrap(x))

    def axpy(self, alpha, x, y):
        out = alpha * _unwrap(x) + _unwrap(y)
        if isinstance(y, NDArray):
            return y._write(out)
        return NDArray(out)

    def gemv(self, alpha, a, x, beta=0.0, y=None):
        out = alpha * (_unwrap(a) @ jnp.ravel(_unwrap(x)))
        if y is not None:
            out = out + beta * jnp.ravel(_unwrap(y))
        return NDArray(out)

    def gemm(self, a, b, transpose_a=False, transpose_b=False,
             alpha=1.0, beta=0.0, c=None):
        return gemm(a, b, transpose_a, transpose_b, alpha, beta, c)

    def ger(self, alpha, x, y, a=None):
        out = alpha * jnp.outer(jnp.ravel(_unwrap(x)), jnp.ravel(_unwrap(y)))
        if a is not None:
            out = out + _unwrap(a)
        return NDArray(out)


def getBlasWrapper() -> _BlasWrapper:
    return _BlasWrapper()


# linalg statics (ref: Lapack entry points surfaced on Nd4j in examples)
def svd(a):
    return getBlasWrapper().lapack().gesvd(a)


def cholesky(a) -> NDArray:
    return getBlasWrapper().lapack().potrf(a)


def qr(a):
    return getBlasWrapper().lapack().geqrf(a)


def lu(a):
    return getBlasWrapper().lapack().getrf(a)


def eig(a):
    return getBlasWrapper().lapack().syev(a)


def solve(a, b) -> NDArray:
    return NDArray(jnp.linalg.solve(_unwrap(a), _unwrap(b)))


def lstsq(a, b) -> NDArray:
    sol, *_ = jnp.linalg.lstsq(_unwrap(a), _unwrap(b))
    return NDArray(sol)


def inv(a) -> NDArray:
    return NDArray(jnp.linalg.inv(_unwrap(a)))


def pinv(a) -> NDArray:
    return NDArray(jnp.linalg.pinv(_unwrap(a)))


def det(a) -> float:
    return float(jnp.linalg.det(_unwrap(a)))


def matrixRank(a) -> int:
    return int(jnp.linalg.matrix_rank(_unwrap(a)))


# remaining creation/structure statics
def randUniform(low, high, *shape) -> NDArray:
    """ref: Nd4j.rand(shape, min, max, rng)."""
    key = _rng.next_key()
    return NDArray(jax.random.uniform(key, _shape(shape), _default_dtype,
                                      low, high))


def specialConcat(dim, *arrays) -> NDArray:
    """ref: Nd4j.specialConcat — same contract as concat."""
    return concat(dim, *arrays)


def rollAxis(a, axis, start=0) -> NDArray:
    """ref: Nd4j.rollAxis."""
    return NDArray(jnp.moveaxis(_unwrap(a), axis, start))


def shape(a):
    """ref: Nd4j.shape(INDArray)."""
    return tuple(_unwrap(a).shape)


def order() -> str:
    """ref: Nd4j.order() — logical ordering (XLA owns physical layout)."""
    return "c"


def factory():
    """ref: Nd4j.factory() — the NDArrayFactory; here the module itself."""
    import sys
    return sys.modules[__name__]


def createFromNpzFile(path):
    """ref: Nd4j.createFromNpzFile — dict of name → array."""
    data = np.load(path)
    return {k: NDArray(jnp.asarray(data[k])) for k in data.files}


def writeAsNumpy(arr, path) -> None:
    """ref: Nd4j.writeAsNumpy."""
    writeNumpy(arr, path)


def getCompressor():
    """ref: Nd4j.getCompressor() → BasicNDArrayCompressor. TPU story: PJRT
    buffers are never compressed in-memory; this facade provides the
    at-rest codec (gzip over npy bytes) the reference uses for transport."""
    import gzip

    class _Compressor:
        def compress(self, arr) -> bytes:
            return gzip.compress(toByteArray(arr))

        def decompress(self, data: bytes) -> NDArray:
            return fromByteArray(gzip.decompress(data))

        def setDefaultCompression(self, algo: str):
            return self
    return _Compressor()


def zeros_like(a) -> NDArray:
    return zerosLike(a)


def ones_like(a) -> NDArray:
    return onesLike(a)


def vander(x, n=None) -> NDArray:
    """ref: Nd4j.vander — Vandermonde matrix."""
    return NDArray(jnp.vander(jnp.ravel(_unwrap(x)), n))


def tri(n, m=None, k=0) -> NDArray:
    return NDArray(jnp.tri(n, m, k, dtype=_default_dtype))


def logspace(start, stop, num, base=10.0) -> NDArray:
    return NDArray(jnp.logspace(start, stop, num, base=base,
                                dtype=_default_dtype))


def histogram(a, bins=10):
    h, edges = jnp.histogram(jnp.ravel(_unwrap(a)), bins=bins)
    return NDArray(h), NDArray(edges)


def unique(a) -> NDArray:
    return NDArray(jnp.unique(_unwrap(a)))


def nonzero(a) -> NDArray:
    """Coordinates of nonzero elements, (n, rank) — Nd4j.where analog."""
    return NDArray(jnp.stack(jnp.nonzero(_unwrap(a)), axis=-1))


# re-populate the facade with everything defined after the first pass


def getEnvironment():
    """ref: Nd4j.getEnvironment() → org.nd4j.linalg.factory.Environment —
    runtime introspection knobs (the debug/verbose toggles map to jax's)."""
    class _Env:
        def isCPU(self):
            return jax.default_backend() == "cpu"

        def isTPU(self):
            return jax.default_backend() == "tpu"

        def isDebug(self):
            return bool(jax.config.jax_debug_nans)

        def setDebug(self, v: bool):
            jax.config.update("jax_debug_nans", bool(v))

        def isVerbose(self):
            return jax.config.jax_log_compiles

        def setVerbose(self, v: bool):
            jax.config.update("jax_log_compiles", bool(v))

        def maxThreads(self):
            import os as _os
            return _os.cpu_count()
    return _Env()


def version() -> str:
    """ref: nd4j-common VersionCheck / Nd4j version info."""
    try:
        import importlib.metadata as md
        return md.version("deeplearning4j-tpu")
    except Exception:
        return "0.0.0-dev"




# --------------------------------------------------------------------------
# Tranche-6 statics: the probed remaining Nd4j surface
# (ref: org.nd4j.linalg.factory.Nd4j, SURVEY.md:95-100 J1)

def getDataType():
    """ref: Nd4j.dataType()/getDataType — the global default dtype."""
    return _default_dtype


def setDataType(dtype):
    """ref: Nd4j.setDataType(DataType) — alias of setDefaultDataType."""
    setDefaultDataType(dtype)


def typeConversion(arr, dtype):
    """ref: Nd4j.typeConversion(INDArray, DataTypeEx) — dtype cast through
    the executioner; on TPU a pure `convert_element_type`."""
    a = arr if isinstance(arr, NDArray) else NDArray(arr)
    return a.castTo(dtype)


def batchMmul(matrices_a, matrices_b, transpose_a: bool = False,
              transpose_b: bool = False):
    """ref: Nd4j.batchMmul(INDArray[], INDArray[]) — N independent GEMMs.

    TPU-first divergence: the reference loops gemm over the array pairs
    (libnd4j batched_gemm); here the pairs are STACKED into a single
    (N, m, k) x (N, k, n) `jnp.matmul` so XLA tiles ONE batched MXU
    computation instead of N kernel launches."""
    As = jnp.stack([(_m.buf() if isinstance(_m, NDArray)
                     else jnp.asarray(_m)) for _m in matrices_a])
    Bs = jnp.stack([(_m.buf() if isinstance(_m, NDArray)
                     else jnp.asarray(_m)) for _m in matrices_b])
    if transpose_a:
        As = jnp.swapaxes(As, -1, -2)
    if transpose_b:
        Bs = jnp.swapaxes(Bs, -1, -2)
    out = jnp.matmul(As, Bs)
    return [NDArray(out[i]) for i in range(out.shape[0])]


def createBuffer(data_or_length, dtype=None):
    """ref: Nd4j.createBuffer(...) — DataBuffer creation. PJRT owns device
    storage on TPU (SURVEY N7 yes-D), so the "buffer" equivalent is the
    flat host-side array that backs an NDArray: int/long → zero-filled
    flat buffer of that length; array-like → its flat copy."""
    dt = _dt.resolve(dtype) if dtype is not None else _default_dtype
    if isinstance(data_or_length, (int, np.integer)):
        return NDArray(jnp.zeros((int(data_or_length),), dt))
    flat = jnp.asarray(
        data_or_length.toNumpy() if isinstance(data_or_length, NDArray)
        else data_or_length).reshape(-1)
    return NDArray(flat.astype(dt) if dtype is not None else flat)


def createArrayFromShapeBuffer(buffer, shape_info):
    """ref: Nd4j.createArrayFromShapeBuffer(DataBuffer, DataBuffer/long[])
    — reassemble an array from a flat buffer + shape descriptor. The TPU
    shape descriptor is the logical shape tuple (XLA owns strides)."""
    flat = (buffer.buf() if isinstance(buffer, NDArray)
            else jnp.asarray(buffer)).reshape(-1)
    shape = tuple(int(s) for s in
                  (shape_info.toNumpy().astype(int)
                   if isinstance(shape_info, NDArray) else shape_info))
    return NDArray(flat.reshape(shape))


def versionCheck():
    """ref: nd4j-common org.nd4j.versioncheck.VersionCheck — asserts the
    classpath backend/api versions agree. One wheel here: always
    consistent; returns the version string it validated."""
    return version()


class _DeallocatorService:
    """ref: Nd4j.getDeallocatorService() — JVM-side reference-queue
    deallocator for off-heap buffers. PJRT owns buffer lifetime on TPU
    (SURVEY N7), so the service reports zero queued deallocations."""

    def pendingDeallocations(self):
        return 0

    def deallocate(self, _array=None):  # buffers are GC/PJRT-managed
        return True


_deallocator_service = _DeallocatorService()


def getDeallocatorService():
    return _deallocator_service


class _ShapeInfoProvider:
    """ref: Nd4j.getShapeInfoProvider() → ShapeInfoProvider — builds the
    packed shape-info descriptor. Here the descriptor is (shape, order)."""

    def createShapeInformation(self, shape, order="c"):
        return (tuple(int(s) for s in shape), order)


_shape_info_provider = _ShapeInfoProvider()


def getShapeInfoProvider():
    return _shape_info_provider


_populate_nd4j_facade()
