"""NDArray: the imperative tensor.

TPU-native analog of the reference's NDArray (reference: include/mxnet/ndarray.h,
src/ndarray/ndarray.cc). Design deltas from the reference, chosen for XLA:

* The payload is an immutable `jax.Array` (or a tracer under `hybridize()`'s
  jit trace). Mutation (`a[:] = x`, `a += b`, `copyto`) is implemented by
  functional buffer-swap: the Python `NDArray` object rebinds its `_data` to a
  new array. This preserves the reference's aliasing-visible-mutation semantics
  (reference: NDArray::Chunk shared buffers) without fighting XLA.
* Views (`a[1:3]`, `reshape` sharing, `slice`) carry a `(base, index)` pair and
  always read through the base, so writes through either alias are visible to
  both — the same observable behavior as the reference's zero-copy views.
* Async execution: jax dispatch is already asynchronous (reference engine's
  PushAsync ≙ jax's async dispatch; reference WaitToRead ≙ block_until_ready).
  `MXNET_ENGINE_TYPE=NaiveEngine` forces a block after every op, matching the
  reference's serialized debug engine.
"""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp

from .. import base as _base
from .. import telemetry as _telem
from ..analysis import guard as _guard
from ..base import np_dtype
from ..context import Context, current_context
from ..ops import registry as _reg

__all__ = ["NDArray", "invoke", "array", "zeros", "ones", "full", "empty",
           "arange", "concat", "stack", "waitall", "from_jax", "save", "load",
           "moveaxis", "split_v2"]


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


def _is_tracer_in(raw_args):
    return any(isinstance(a, jax.core.Tracer) for a in raw_args)


class NDArray:
    """A mutable-by-convention tensor over an immutable jax.Array payload."""

    __slots__ = ("_data", "_ctx", "_base", "_idx", "_grad", "_grad_req",
                 "_autograd_node", "_tape_used", "_stype", "_deferred",
                 "__weakref__")

    def __init__(self, data, ctx=None, base=None, idx=None, stype="default"):
        self._data = data          # jax.Array | tracer | None (if view)
        self._ctx = ctx or current_context()
        self._base = base          # parent NDArray when this is a view
        self._idx = idx            # index into parent
        self._grad = None
        self._grad_req = "null"
        self._autograd_node = None  # set when this array is a recorded output
        self._tape_used = False     # set when consumed by a recorded op
        self._stype = stype
        self._deferred = None

    # ------------------------------------------------------------------
    # raw payload access (functional view chain)
    # ------------------------------------------------------------------
    def _read(self):
        """Current payload; views read through their base so writes to the
        base are visible (reference: zero-copy NDArray::Slice)."""
        if self._deferred is not None:
            # async engine semantics: the op that produced this array failed;
            # its stored exception surfaces when the value is touched
            # (reference: ThreadedVar exception_ptr, test_exc_handling.py)
            raise self._deferred[0]
        if self._base is None:
            return self._data
        return self._base._read()[self._idx]

    def _write(self, value):
        """Replace the full payload (functional update through view chains)."""
        if self._base is None:
            self._data = value
        else:
            self._base._write(self._base._read().at[self._idx].set(value))

    @property
    def data_jax(self):
        """The underlying jax.Array (public escape hatch to raw JAX)."""
        return self._read()

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._read().shape)

    @property
    def dtype(self):
        return _np.dtype(self._read().dtype)

    @property
    def size(self):
        s = 1
        for d in self.shape:
            s *= d
        return s

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return self._stype

    @property
    def T(self):
        return invoke("transpose", self)

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        try:
            arr = self.asnumpy()
            body = str(arr)
        except Exception as e:  # tracer payloads can't be printed as values
            body = "<unrealized: %s>" % type(self._read()).__name__
        return "%s\n<NDArray %s @%s>" % (
            body, "x".join(str(d) for d in self.shape), self._ctx)

    # ------------------------------------------------------------------
    # sync points (reference: WaitToRead / WaitForAll / asnumpy)
    # ------------------------------------------------------------------
    def asnumpy(self):
        """Blocking copy to numpy. reference: NDArray::SyncCopyToCPU — the
        canonical sync point where async errors surface."""
        if _telem.ENABLED:
            # the classic hidden stall under async dispatch: every forced
            # device→host copy shows up as a counter
            _telem.inc("ndarray.sync.asnumpy")
        raw = self._read()
        if _guard.ACTIVE and _is_tracer(raw):
            # MXNET_TPU_TRACE_GUARD: a host sync on a traced value has no
            # value to sync — surface the mxnet-level API (and count it)
            # before jax's generic concretization error
            _guard.host_sync("asnumpy")
        return _np.asarray(raw)

    def wait_to_read(self):
        if _telem.ENABLED:
            _telem.inc("ndarray.sync.wait_to_read")
        arr = self._read()
        if _guard.ACTIVE and _is_tracer(arr):
            _guard.host_sync("wait_to_read")
        jax.block_until_ready(arr)
        return self

    wait_to_write = wait_to_read

    def item(self):
        return self.asnumpy().item()

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def __bool__(self):
        if self.size == 1:
            return bool(self.asnumpy())
        raise ValueError("Truth value of multi-element NDArray is ambiguous")

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __index__(self):
        if self.size == 1 and _np.issubdtype(self.dtype, _np.integer):
            return int(self.asscalar())
        raise TypeError("only integer scalar arrays can be converted to index")

    # numpy interop
    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # ------------------------------------------------------------------
    # movement / copies
    # ------------------------------------------------------------------
    def copy(self):
        from .. import autograd
        if autograd.is_recording():
            # copy is a recorded op (reference: _copyto with FGradient);
            # a raw buffer copy would silently detach the tape
            return _invoke("_copyto", self)
        return NDArray(self._read(), ctx=self._ctx)

    def copyto(self, other):
        """reference: NDArray::CopyFromTo — cross-device async copy."""
        from .. import autograd
        if isinstance(other, NDArray):
            if autograd.is_recording():
                # writing into an array already in the recorded graph
                # would silently reroute its consumers' gradients
                other._check_inplace_ok()
                # cast op (not identity) so the recorded vjp converts the
                # cotangent back to the source dtype
                _invoke("cast", self, dtype=other.dtype, out=other)
                # _invoke's out= path handles dtype but not device; keep
                # the non-recording branch's cross-device commitment
                other._write(jax.device_put(other._read(),
                                            other._ctx.jax_device))
                return other
            val = self._read()
            if other.dtype != self.dtype:
                val = val.astype(other.dtype)
            other._write(jax.device_put(val, other._ctx.jax_device))
            return other
        if isinstance(other, Context):
            if autograd.is_recording():
                out = _invoke("_copyto", self)
                out._write(jax.device_put(out._read(), other.jax_device))
                out._ctx = other
                return out
            return NDArray(jax.device_put(self._read(), other.jax_device), ctx=other)
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context):
        if context == self._ctx:
            return self
        return self.copyto(context)

    as_in_ctx = as_in_context

    def astype(self, dtype, copy=True):
        dt = np_dtype(dtype)
        if not copy and dt == self.dtype:
            return self
        return invoke("cast", self, dtype=dt)

    def detach(self):
        """Return a copy detached from the autograd tape."""
        out = NDArray(self._read(), ctx=self._ctx, base=self._base, idx=self._idx)
        return out

    # ------------------------------------------------------------------
    # autograd (reference: MXAutograd* via python/mxnet/autograd.py)
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Mark this array as requiring gradient (reference:
        Imperative::MarkVariables). `stype='row_sparse'` allocates a
        row-sparse grad buffer (reference: attach_grad stype arg)."""
        from .. import autograd
        if stype == "row_sparse":
            from . import sparse as _sp
            self._grad = _sp.zeros("row_sparse", self.shape, ctx=self._ctx,
                                   dtype=self.dtype)
        else:
            self._grad = zeros(self.shape, ctx=self._ctx, dtype=self.dtype)
        self._grad_req = grad_req
        autograd.mark_variable(self, grad_req)

    @property
    def grad(self):
        return self._grad

    def zero_grad(self):
        if self._grad is not None:
            self._grad._write(jnp.zeros_like(self._grad._read()))

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    @staticmethod
    def _is_basic_index(key):
        if isinstance(key, (slice, int, type(None), type(Ellipsis))):
            return True
        if isinstance(key, tuple):
            return all(isinstance(k, (slice, int, type(None), type(Ellipsis)))
                       for k in key)
        return False

    def __getitem__(self, key):
        from .. import autograd
        if isinstance(key, NDArray):
            key = key.data_jax
        if autograd.is_recording():
            # under record() slicing must live on the tape: a raw view (or
            # a bare gather copy) would silently detach the gradient
            # (reference: slicing lowers to slice/gather ops with
            # FGradient). Mutation of recorded arrays is forbidden anyway,
            # so losing view aliasing here changes nothing observable.
            return _invoke("_internal_getitem", self, index=key)
        if NDArray._is_basic_index(key):
            # zero-copy view semantics (reference: NDArray::Slice/At)
            return NDArray(None, ctx=self._ctx, base=self, idx=key)
        # advanced indexing → gather (a copy, as in the reference)
        return NDArray(self._read()[key], ctx=self._ctx)

    def __setitem__(self, key, value):
        self._check_inplace_ok()
        if isinstance(key, NDArray):
            key = key.data_jax
        if isinstance(value, NDArray):
            value = value._read()
        cur = self._read()
        if key is Ellipsis or (isinstance(key, slice) and key == slice(None)):
            val = jnp.broadcast_to(jnp.asarray(value, dtype=cur.dtype), cur.shape)
            self._write(val)
        else:
            self._write(cur.at[key].set(jnp.asarray(value, dtype=cur.dtype)))

    def slice(self, begin, end, step=None):
        return invoke("slice", self, begin=begin, end=end, step=step)

    def slice_axis(self, axis, begin, end):
        return invoke("slice_axis", self, axis=axis, begin=begin, end=end)

    def take(self, indices, axis=0, mode="clip"):
        return invoke("take", self, indices, axis=axis, mode=mode)

    def pick(self, index, axis=-1, keepdims=False):
        return invoke("pick", self, index, axis=axis, keepdims=keepdims)

    # ------------------------------------------------------------------
    # shape ops
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if kwargs.get("shape") is not None:
            shape = tuple(kwargs["shape"])
        return invoke("reshape", self, shape=shape)

    def reshape_like(self, other):
        return invoke("reshape", self, shape=other.shape)

    def expand_dims(self, axis):
        return invoke("expand_dims", self, axis=axis)

    def squeeze(self, axis=None):
        return invoke("squeeze", self, axis=axis)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke("transpose", self, axes=axes if axes else None)

    def swapaxes(self, dim1, dim2):
        return invoke("swapaxes", self, dim1=dim1, dim2=dim2)

    def flatten(self):
        return invoke("flatten", self)

    def broadcast_to(self, shape):
        return invoke("broadcast_to", self, shape=tuple(shape))

    def broadcast_like(self, other):
        return invoke("broadcast_to", self, shape=other.shape)

    def tile(self, reps):
        return invoke("tile", self, reps=reps)

    def repeat(self, repeats, axis=None):
        return invoke("repeat", self, repeats=repeats, axis=axis)

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke("split", self, num_outputs=num_outputs, axis=axis,
                      squeeze_axis=squeeze_axis)

    # ------------------------------------------------------------------
    # arithmetic — magic methods route through the op registry so autograd
    # and hybridize tracing see every operation
    # ------------------------------------------------------------------
    def __add__(self, other):
        return invoke("broadcast_add", self, other)

    def __radd__(self, other):
        return invoke("broadcast_add", self, other)

    def __sub__(self, other):
        return invoke("broadcast_sub", self, other)

    def __rsub__(self, other):
        return invoke("broadcast_sub", other, self)

    def __mul__(self, other):
        return invoke("broadcast_mul", self, other)

    def __rmul__(self, other):
        return invoke("broadcast_mul", self, other)

    def __truediv__(self, other):
        return invoke("broadcast_div", self, other)

    def __rtruediv__(self, other):
        return invoke("broadcast_div", other, self)

    def __mod__(self, other):
        return invoke("broadcast_mod", self, other)

    def __rmod__(self, other):
        return invoke("broadcast_mod", other, self)

    def __pow__(self, other):
        return invoke("broadcast_power", self, other)

    def __rpow__(self, other):
        return invoke("broadcast_power", other, self)

    def __neg__(self):
        return invoke("negative", self)

    def __abs__(self):
        return invoke("abs", self)

    # in-place: buffer-swap preserving aliasing through views. Disallowed
    # while recording — rebinding an array's tape node mid-record would
    # corrupt gradient routing for earlier uses of the same array. This
    # matches the reference ("Inplace operations (+=, -=, x[:]=) are not
    # supported when recording with autograd", src/imperative/imperative.cc).
    def _check_inplace_ok(self):
        from .. import autograd
        if autograd.is_recording() and (self._autograd_node is not None or
                                        self._tape_used):
            raise _base.MXNetError(
                "Inplace operations (+=, -=, x[:]=, etc) are not supported "
                "on arrays already used in a computation when recording with "
                "autograd (matches reference semantics)")

    def _inplace(self, opname, other):
        self._check_inplace_ok()
        res = invoke(opname, self, other)
        self._write(res._read().astype(self._read().dtype))
        self._autograd_node = res._autograd_node
        return self

    def __iadd__(self, other):
        return self._inplace("broadcast_add", other)

    def __isub__(self, other):
        return self._inplace("broadcast_sub", other)

    def __imul__(self, other):
        return self._inplace("broadcast_mul", other)

    def __itruediv__(self, other):
        return self._inplace("broadcast_div", other)

    # comparisons
    def __eq__(self, other):
        return invoke("broadcast_equal", self, other)

    def __ne__(self, other):
        return invoke("broadcast_not_equal", self, other)

    def __lt__(self, other):
        return invoke("broadcast_lesser", self, other)

    def __le__(self, other):
        return invoke("broadcast_lesser_equal", self, other)

    def __gt__(self, other):
        return invoke("broadcast_greater", self, other)

    def __ge__(self, other):
        return invoke("broadcast_greater_equal", self, other)

    __hash__ = object.__hash__

    # ------------------------------------------------------------------
    # reductions & math conveniences (thin wrappers over registry ops)
    # ------------------------------------------------------------------
    def sum(self, axis=None, keepdims=False):
        return invoke("sum", self, axis=axis, keepdims=keepdims)

    def mean(self, axis=None, keepdims=False):
        return invoke("mean", self, axis=axis, keepdims=keepdims)

    def max(self, axis=None, keepdims=False):
        return invoke("max", self, axis=axis, keepdims=keepdims)

    def min(self, axis=None, keepdims=False):
        return invoke("min", self, axis=axis, keepdims=keepdims)

    def prod(self, axis=None, keepdims=False):
        return invoke("prod", self, axis=axis, keepdims=keepdims)

    def argmax(self, axis=None, keepdims=False):
        return invoke("argmax", self, axis=axis, keepdims=keepdims)

    def argmin(self, axis=None, keepdims=False):
        return invoke("argmin", self, axis=axis, keepdims=keepdims)

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke("norm", self, ord=ord, axis=axis, keepdims=keepdims)

    def abs(self):
        return invoke("abs", self)

    def sqrt(self):
        return invoke("sqrt", self)

    def exp(self):
        return invoke("exp", self)

    def log(self):
        return invoke("log", self)

    def clip(self, a_min, a_max):
        return invoke("clip", self, a_min=a_min, a_max=a_max)

    def sign(self):
        return invoke("sign", self)

    def square(self):
        return invoke("square", self)

    def relu(self):
        return invoke("relu", self)

    def sigmoid(self):
        return invoke("sigmoid", self)

    def tanh(self):
        return invoke("tanh", self)

    def softmax(self, axis=-1):
        return invoke("softmax", self, axis=axis)

    def log_softmax(self, axis=-1):
        return invoke("log_softmax", self, axis=axis)

    def one_hot(self, depth, on_value=1.0, off_value=0.0):
        return invoke("one_hot", self, depth=depth, on_value=on_value,
                      off_value=off_value)

    def dot(self, other, **kwargs):
        return invoke("dot", self, other, **kwargs)

    def tostype(self, stype):
        from . import sparse as _sp
        return _sp.cast_storage(self, stype)


# ---------------------------------------------------------------------------
# the generic imperative invoke — analog of MXImperativeInvokeEx →
# Imperative::Invoke (reference: src/c_api/c_api_ndarray.cc,
# src/imperative/imperative.cc). Handles unwrap → dispatch → wrap → record.
# ---------------------------------------------------------------------------
def _wrap_out(raw, ctx):
    if isinstance(raw, (tuple, list)):
        return [NDArray(r, ctx=ctx) for r in raw]
    return NDArray(raw, ctx=ctx)


# installed by mxnet_tpu.contrib.amp.init(); wraps op fns with dtype casts
_AMP_WRAP = None
# toggled by mxnet_tpu.profiler.set_state(); plain bool so the off-path
# costs one global read per dispatch
_PROFILE_IMPERATIVE = False


def invoke(op_name, *args, out=None, **kwargs):
    if _telem.ENABLED:
        _telem.inc("ndarray.invoke")
    if _PROFILE_IMPERATIVE:
        from .. import profiler as _profiler
        import time as _time
        t0 = _time.perf_counter()
        try:
            return _invoke(op_name, *args, out=out, **kwargs)
        finally:
            # host dispatch time; device time comes from the jax trace layer
            _profiler.record_op(op_name, _time.perf_counter() - t0)
    return _invoke(op_name, *args, out=out, **kwargs)


def _poisoned_outputs(exc_entry, op, ctx, out=None):
    """Outputs of an async op whose execution failed: carry the exception
    to the next sync point instead of raising at dispatch (reference:
    dependency-chain exception propagation, src/engine/threaded_engine.cc
    OnCompleteStatic storing exception_ptr on the output vars)."""
    outs = []
    for _ in range(max(1, op.num_outputs)):
        o = NDArray(None, ctx=ctx)
        o._deferred = exc_entry
        outs.append(o)
    if out is not None:
        dst = out if isinstance(out, (tuple, list)) else [out]
        for d, s in zip(dst, outs):
            d._deferred = exc_entry
            d._data, d._base, d._idx = None, None, None
        return out
    return outs[0] if op.num_outputs == 1 and len(outs) == 1 else outs


# --------------------------------------------------------------------------
# signature-counted backward cache for rule-less recorded ops.
#
# The generic tape pays a jax.vjp re-trace on EVERY recorded call. Once the
# same (op, kwargs, input signature) has been seen a few times — a training
# loop — the backward is compiled ONCE as a jitted recompute program
# (jax.vjp inside jit) and reused every step. One-off signatures (numeric
# sweeps, ad-hoc shapes) never reach the threshold and keep the cheap
# uncompiled path; compile cost is only spent where it amortizes.
# --------------------------------------------------------------------------
_SIG_SEEN: dict = {}
_BWD_PROGS: dict = {}
_BWD_THRESHOLD = 3
_BWD_CACHE_MAX = 512


def _sig_key(op_name, fn, raw_args, kwargs, nd_positions, inputs_raw):
    try:
        static = tuple(
            (i, a) for i, a in enumerate(raw_args) if i not in nd_positions)
        kw = tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in kwargs.items()))
        avals = tuple((tuple(a.shape), str(a.dtype)) for a in inputs_raw)
        # id(fn) pins the RESOLVED implementation (op.fn vs a Pallas
        # tpu_impl, either may be switched/registered at runtime) so a
        # cached backward can never differentiate a different fn than
        # the forward ran
        key = (op_name, id(fn), static, kw, avals)
        hash(key)
        return key
    except TypeError:
        return None


def _cached_backward(op_name, fn, raw_args, kwargs, nd_positions,
                     inputs_raw):
    """Jitted backward program for a hot signature, else None."""
    if any(_is_tracer(a) for a in inputs_raw):
        return None
    key = _sig_key(op_name, fn, raw_args, kwargs, nd_positions,
                   inputs_raw)
    if key is None:
        return None
    if len(_SIG_SEEN) >= 16384:   # bound the counter table itself
        _SIG_SEEN.clear()
    seen = _SIG_SEEN.get(key, 0) + 1
    _SIG_SEEN[key] = seen
    if seen < _BWD_THRESHOLD:
        return None
    prog = _BWD_PROGS.get(key)
    if prog is None:
        # null the dynamic slots: the closure must NOT retain the first
        # hot call's device buffers
        pos_set = set(nd_positions)
        fixed = [None if i in pos_set else a
                 for i, a in enumerate(raw_args)]
        positions = list(nd_positions)
        kw = dict(kwargs)

        def rebuilt(*arrs):
            full = list(fixed)
            for p, a in zip(positions, arrs):
                full[p] = a
            return fn(*full, **kw)

        @jax.jit
        def prog(*ins_and_cot):
            ins = ins_and_cot[:-1]
            cot = ins_and_cot[-1]
            return jax.vjp(rebuilt, *ins)[1](cot)
        if len(_BWD_PROGS) >= _BWD_CACHE_MAX:
            _BWD_PROGS.clear()   # simple bound; rebuilt on demand
            _SIG_SEEN.clear()
        _BWD_PROGS[key] = prog
    return prog


def _invoke(op_name, *args, out=None, **kwargs):
    op = _reg.get(op_name)
    from .. import autograd

    ctx = None
    raw_args = []
    nd_positions = []
    poisoned = None
    for i, a in enumerate(args):
        if isinstance(a, NDArray):
            if a._deferred is not None and poisoned is None:
                poisoned = a._deferred
            nd_positions.append(i)
            if ctx is None:
                ctx = a._ctx
            raw_args.append(None if poisoned is not None else a._read())
        else:
            raw_args.append(a)
    if poisoned is not None:
        # a dependency already failed: poison downstream, don't raise here
        return _poisoned_outputs(poisoned, op,
                                 ctx or current_context(), out)
    if ctx is None:
        ctx = kwargs.pop("ctx", None) or current_context()
    elif "ctx" in kwargs:
        kwargs.pop("ctx")

    if op.random:
        from .. import random as _random
        kwargs.setdefault("key", _random.take_key(ctx))

    on_tpu = ctx.device_type in ("gpu", "tpu")
    fn = op.best_fn(on_tpu)
    if _AMP_WRAP is not None:
        fn = _AMP_WRAP(fn, op_name)

    # reference records every op executed under record() (Imperative::RecordOp);
    # grads later flow only to marked variables, but unmarked ones can still be
    # queried via autograd.grad()
    recording = (autograd.is_recording() and op.differentiable and nd_positions)

    try:
        if recording:
            nd_inputs = [args[p] for p in nd_positions]

            def closed(*arrs):
                full = list(raw_args)
                for p, a in zip(nd_positions, arrs):
                    full[p] = a
                return fn(*full, **kwargs)
            override = None
            if op.record_override is not None:
                override = op.record_override(raw_args, kwargs, nd_inputs, fn)
            if override is not None:
                out_raw, vjp_fn, primal = override
            elif op.vjp_rule is not None and _AMP_WRAP is None:
                # FGradient-style rule: plain forward (no per-call
                # jax.vjp trace); the rule computes cotangents directly
                out_raw = fn(*raw_args, **kwargs)
                vjp_fn = functools.partial(op.vjp_rule, out=out_raw,
                                           raw_args=raw_args, kwargs=kwargs,
                                           nd_positions=nd_positions)
                primal = closed
            else:
                inputs_raw = [raw_args[p] for p in nd_positions]
                cached = None
                if _AMP_WRAP is None:  # AMP wraps fn per-call: uncacheable
                    cached = _cached_backward(op_name, fn, raw_args,
                                              kwargs, nd_positions,
                                              inputs_raw)
                if cached is not None:
                    # hot signature: plain forward + a jit-compiled
                    # recompute-backward program (traced/compiled once,
                    # reused every step — the CachedOp-for-the-tape idea)
                    out_raw = fn(*raw_args, **kwargs)
                    vjp_fn = functools.partial(cached, *inputs_raw)
                else:
                    out_raw, vjp_fn = jax.vjp(closed, *inputs_raw)
                primal = closed
            outputs = _wrap_out(out_raw, ctx)
            autograd.record_op(op_name, nd_inputs,
                               outputs if isinstance(outputs, list)
                               else [outputs],
                               vjp_fn, primal_fn=primal)
        else:
            out_raw = fn(*raw_args, **kwargs)
            outputs = _wrap_out(out_raw, ctx)
    except Exception as e:
        if _base.is_naive_engine() or _is_tracer_in(raw_args):
            raise  # sync-debug mode (or inside a jit trace): fail in place
        return _poisoned_outputs((e, op_name), op, ctx, out)

    if _base.is_naive_engine():
        for o in (outputs if isinstance(outputs, list) else [outputs]):
            if not _is_tracer(o._read()):
                o.wait_to_read()

    if out is not None:
        src = outputs if isinstance(outputs, list) else [outputs]
        dst = out if isinstance(out, (tuple, list)) else [out]
        for s, d in zip(src, dst):
            d._write(s._read().astype(d._read().dtype))
            d._autograd_node = s._autograd_node
        return out

    if isinstance(outputs, list) and op.num_outputs == 1 and len(outputs) == 1:
        return outputs[0]
    return outputs


# ---------------------------------------------------------------------------
# creation (reference: src/operator/tensor/init_op.cc + python veneer)
# ---------------------------------------------------------------------------
def _put(arr, ctx):
    ctx = ctx or current_context()
    if _is_tracer(arr):
        return NDArray(arr, ctx=ctx)
    return NDArray(jax.device_put(arr, ctx.jax_device), ctx=ctx)


def from_jax(arr, ctx=None):
    """Wrap a raw jax.Array / tracer without copying."""
    return NDArray(arr, ctx=ctx or current_context())


def array(source_array, ctx=None, dtype=None):
    """reference: python/mxnet/ndarray/utils.py (array) — defaults to float32
    regardless of source dtype, like the reference."""
    if isinstance(source_array, NDArray):
        src = source_array._read()
        dt = np_dtype(dtype) if dtype is not None else src.dtype
        return _put(src.astype(dt), ctx)
    if _is_tracer(source_array):
        return NDArray(source_array, ctx=ctx or current_context())
    src = _np.asarray(source_array)
    if dtype is None:
        dtype = _np.float32  # MXNet semantics: float32 even for float64 input
    return _put(jnp.asarray(src, dtype=np_dtype(dtype)), ctx)


def zeros(shape, ctx=None, dtype=None, stype=None):
    if isinstance(shape, int):
        shape = (shape,)
    return _put(jnp.zeros(shape, dtype=np_dtype(dtype)), ctx)


def ones(shape, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    return _put(jnp.ones(shape, dtype=np_dtype(dtype)), ctx)


def full(shape, val, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    return _put(jnp.full(shape, val, dtype=np_dtype(dtype)), ctx)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    arr = jnp.arange(start, stop, step, dtype=np_dtype(dtype))
    if repeat != 1:
        arr = jnp.repeat(arr, repeat)
    return _put(arr, ctx)


def concat(*arrays, dim=1):
    return invoke("concat", *arrays, dim=dim)


def stack(*arrays, axis=0):
    return invoke("stack", *arrays, axis=axis)


def moveaxis(data, source, destination):
    return invoke("moveaxis", data, source=source, destination=destination)


def split_v2(ary, indices_or_sections, axis=0, squeeze_axis=False):
    if isinstance(indices_or_sections, (list, tuple)):
        indices_or_sections = tuple(indices_or_sections)
    return invoke("_split_v2", ary, indices_or_sections=indices_or_sections,
                  axis=axis, squeeze_axis=squeeze_axis)


def waitall():
    """reference: MXNDArrayWaitAll — barrier on all pending async work."""
    try:
        jax.effects_barrier()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# save / load (reference: mx.nd.save/load → dmlc serialized dict; we keep the
# same entry points; binary format implemented in ..io.params_serde)
# ---------------------------------------------------------------------------
def save(fname, data):
    from ..io import params_serde
    params_serde.save_ndarrays(fname, data)


def load(fname):
    from ..io import params_serde
    return params_serde.load_ndarrays(fname)
