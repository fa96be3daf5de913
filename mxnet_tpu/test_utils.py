"""Test harness — the per-op numeric oracle.

Reference capability: `python/mxnet/test_utils.py` —
`check_numeric_gradient` (:790, finite differences vs symbolic grad),
`check_symbolic_forward`/`check_symbolic_backward` (:926,:1054),
`assert_almost_equal` (:470), `rand_ndarray` (:339), and
`check_consistency` (:1207), the cross-backend oracle (cpu-vs-gpu in the
reference, cpu-vs-tpu here).  SURVEY §4.1 calls this the single most
important harness to reproduce.
"""

from __future__ import annotations

import numpy as np

from . import context as ctx_mod
from . import ndarray as nd
from .ndarray import NDArray

__all__ = [
    "default_context", "assert_almost_equal", "almost_equal", "same",
    "rand_ndarray", "rand_shape_nd", "random_arrays",
    "numeric_grad", "check_numeric_gradient",
    "check_symbolic_forward", "check_symbolic_backward",
    "check_consistency", "list_backends", "tiny_attention_lm",
    "dense_decode_reference",
]

_DEFAULT_RTOL = {np.dtype(np.float16): 1e-2, np.dtype(np.float32): 1e-4,
                 np.dtype(np.float64): 1e-5}
_DEFAULT_ATOL = {np.dtype(np.float16): 1e-2, np.dtype(np.float32): 1e-5,
                 np.dtype(np.float64): 1e-7}


def default_context():
    return ctx_mod.current_context()


def same(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def almost_equal(a, b, rtol=None, atol=None):
    a, b = np.asarray(a), np.asarray(b)
    rtol = rtol if rtol is not None else \
        _DEFAULT_RTOL.get(a.dtype, 1e-4)
    atol = atol if atol is not None else \
        _DEFAULT_ATOL.get(a.dtype, 1e-5)
    return np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=True)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b")):
    a_np = a.asnumpy() if isinstance(a, NDArray) else np.asarray(a)
    b_np = b.asnumpy() if isinstance(b, NDArray) else np.asarray(b)
    rtol = rtol if rtol is not None else \
        _DEFAULT_RTOL.get(a_np.dtype, 1e-4)
    atol = atol if atol is not None else \
        _DEFAULT_ATOL.get(a_np.dtype, 1e-5)
    np.testing.assert_allclose(
        a_np, b_np, rtol=rtol, atol=atol, equal_nan=True,
        err_msg="%s and %s differ" % names)


def rand_shape_nd(ndim, dim=10):
    return tuple(np.random.randint(1, dim + 1, size=ndim))


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 ctx=None):
    dtype = dtype or np.float32
    if stype == "default":
        return nd.array(np.random.uniform(-1, 1, shape).astype(dtype),
                        ctx=ctx)
    from .ndarray import sparse as _sp
    density = 0.5 if density is None else density
    arr = np.random.uniform(-1, 1, shape).astype(dtype)
    mask = np.random.uniform(0, 1, shape[:1]) < density
    arr[~mask] = 0
    dense = nd.array(arr, ctx=ctx)
    if stype == "row_sparse":
        return dense.tostype("row_sparse")
    if stype == "csr":
        arr2 = arr * (np.random.uniform(0, 1, shape) < density)
        return nd.array(arr2).tostype("csr")
    raise ValueError("unknown stype %r" % stype)


def random_arrays(*shapes):
    arrays = [np.random.randn(*s).astype(np.float32) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def _as_location(sym, location):
    """Normalize user-provided inputs to {arg_name: numpy}."""
    args = sym.list_arguments()
    if isinstance(location, dict):
        return {k: np.asarray(v.asnumpy() if isinstance(v, NDArray) else v)
                for k, v in location.items()}
    return {name: np.asarray(v.asnumpy() if isinstance(v, NDArray) else v)
            for name, v in zip(args, location)}


def _bind(sym, location, aux_states=None, grad_req="write", ctx=None,
          dtype=None):
    ctx = ctx or default_context()
    args = {}
    grads = {}
    for name, v in location.items():
        v = np.asarray(v, dtype=dtype) if dtype else np.asarray(v)
        args[name] = nd.array(v, ctx=ctx)
        grads[name] = nd.zeros(v.shape, ctx=ctx, dtype=v.dtype)
    aux = {k: nd.array(np.asarray(v), ctx=ctx)
           for k, v in (aux_states or {}).items()}
    return sym.bind(ctx=ctx, args=args, args_grad=grads,
                    grad_req=grad_req, aux_states=aux)


def numeric_grad(f, location, eps=1e-4):
    """Central-difference gradients of scalar-valued f(dict)->float."""
    grads = {}
    for name, v in location.items():
        v = np.asarray(v, dtype=np.float64)
        g = np.zeros_like(v)
        flat = v.reshape(-1)
        gflat = g.reshape(-1)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            fp = f({**location, name: v})
            flat[i] = orig - eps
            fm = f({**location, name: v})
            flat[i] = orig
            gflat[i] = (fp - fm) / (2 * eps)
        grads[name] = g
    return grads


def check_numeric_gradient(sym, location, aux_states=None, eps=1e-3,
                           rtol=1e-2, atol=1e-4, grad_nodes=None,
                           ctx=None):
    """Symbolic gradients vs central finite differences
    (reference: test_utils.py:790).

    The comparison runs in float64 — finite differences in f32 would
    drown real gradient bugs in rounding noise.
    """
    import jax
    with jax.enable_x64():
        location = _as_location(sym, location)
        location = {k: np.asarray(v, np.float64)
                    for k, v in location.items()}
        aux64 = {k: np.asarray(
                    v.asnumpy() if isinstance(v, NDArray) else v,
                    np.float64)
                 for k, v in (aux_states or {}).items()}
        grad_nodes = grad_nodes or list(location)
        exe = _bind(sym, location, aux64, ctx=ctx)
        outs = exe.forward(is_train=True)
        # random fixed projection makes the output scalar
        rs = np.random.RandomState(0)
        proj = [rs.normal(0, 1, o.shape).astype(np.float64)
                for o in outs]
        exe.backward(out_grads=[nd.array(p) for p in proj])
        sym_grads = {n: exe.grad_dict[n].asnumpy() for n in grad_nodes}

        # ONE probe executor reused across every finite-difference
        # evaluation: a fresh _bind per probe would build fresh jit
        # closures and recompile the forward program for EVERY one of
        # the 2-per-element evaluations (minutes per test, the reason
        # these suites used to be unaffordable).  Fresh-bind semantics
        # are restored by hand each call: the PRNG key rewinds to the
        # bind-time key (stochastic ops replay identical masks, so f
        # stays deterministic) and train-mode aux updates (BatchNorm
        # stats) are rolled back to the bind-time handles.
        probe = _bind(sym, location, aux64, ctx=ctx)
        key0 = probe._key
        aux0 = {n: a._data for n, a in probe.aux_dict.items()}

        def f(loc):
            probe._key = key0
            for n, a in probe.aux_dict.items():
                a._data = aux0[n]
            os = probe.forward(is_train=True, **{**location, **loc})
            return sum(float(np.sum(o.asnumpy() * p))
                       for o, p in zip(os, proj))

        num_grads = numeric_grad(
            f, {n: location[n] for n in grad_nodes}, eps=eps)
        for n in grad_nodes:
            np.testing.assert_allclose(
                sym_grads[n], num_grads[n], rtol=rtol, atol=atol,
                err_msg="numeric vs symbolic gradient mismatch for %r "
                        "of %s" % (n, sym.list_outputs()))


def check_symbolic_forward(sym, location, expected, rtol=1e-4, atol=1e-5,
                           aux_states=None, ctx=None):
    """Forward outputs vs expected numpy arrays (reference: :926)."""
    location = _as_location(sym, location)
    exe = _bind(sym, location, aux_states, ctx=ctx)
    outs = exe.forward(is_train=False)
    if not isinstance(expected, (list, tuple)):
        expected = [expected]
    for o, e in zip(outs, expected):
        np.testing.assert_allclose(o.asnumpy(), np.asarray(e), rtol=rtol,
                                   atol=atol)
    return outs


def check_symbolic_backward(sym, location, out_grads, expected,
                            rtol=1e-4, atol=1e-5, aux_states=None,
                            grad_req="write", ctx=None):
    """Backward input-gradients vs expected (reference: :1054)."""
    location = _as_location(sym, location)
    exe = _bind(sym, location, aux_states, grad_req=grad_req, ctx=ctx)
    exe.forward(is_train=True)
    exe.backward(out_grads=[nd.array(np.asarray(g)) for g in
                            (out_grads if isinstance(out_grads,
                                                     (list, tuple))
                             else [out_grads])])
    if isinstance(expected, dict):
        items = expected.items()
    else:
        items = zip(sym.list_arguments(), expected)
    for name, e in items:
        if e is None:
            continue
        np.testing.assert_allclose(
            exe.grad_dict[name].asnumpy(), np.asarray(e), rtol=rtol,
            atol=atol, err_msg="input gradient mismatch for %r" % name)
    return exe.grad_dict


def list_backends():
    """JAX platforms usable as consistency-check contexts."""
    import jax
    out = []
    for platform in ("cpu", "tpu", "gpu"):
        try:
            if jax.devices(platform):
                out.append(platform)
        except RuntimeError:
            pass
    return out


def _ctx_for(backend):
    return ctx_mod.cpu(0) if backend == "cpu" else \
        ctx_mod.Context("tpu" if backend == "tpu" else "gpu", 0)


def check_consistency(sym, location=None, shapes=None, aux_states=None,
                      backends=None, rtol=1e-4, atol=1e-5,
                      grad_req="write", seed=0):
    """Run the same symbol on every available backend and assert outputs
    and gradients agree — the cross-backend oracle
    (reference: test_utils.py:1207, cpu-vs-gpu there, cpu-vs-tpu here).

    When only one backend exists (CI runs on the CPU mesh), degrades to a
    determinism check: two independent executions must agree bitwise.
    """
    backends = backends or list_backends()
    if location is None or (shapes is not None
                            and isinstance(location, dict)):
        # shapes drive random values; an optional partial location dict
        # overrides specific inputs (index/range args that must be valid)
        rs = np.random.RandomState(seed)
        overrides = dict(location or {})
        location = {n: overrides.get(
            n, rs.normal(0, 1, s).astype(np.float32))
            for n, s in shapes.items()}
    else:
        location = _as_location(sym, location)
    rs = np.random.RandomState(seed + 1)
    results = []
    for backend in (backends if len(backends) > 1
                    else backends * 2):
        ctx = _ctx_for(backend)
        exe = _bind(sym, location, aux_states, grad_req=grad_req,
                    ctx=ctx)
        outs = exe.forward(is_train=True)
        if grad_req == "null":
            # forward-only op (integer/index outputs have no gradient)
            results.append(([o.asnumpy() for o in outs], {}, None,
                            backend))
            continue
        proj = [rs.normal(0, 1, o.shape).astype(np.float32)
                for o in outs] if not results else results[0][2]
        # cotangents must live on THIS executor's backend, not the
        # session-default device (mixed cpu+tpu sessions)
        exe.backward(out_grads=[nd.array(p, ctx=ctx) for p in proj])
        grads = {n: exe.grad_dict[n].asnumpy()
                 for n in exe.grad_dict}
        results.append(([o.asnumpy() for o in outs], grads, proj,
                        backend))
    ref_outs, ref_grads, _, ref_b = results[0]
    for outs, grads, _, b in results[1:]:
        for i, (o, r) in enumerate(zip(outs, ref_outs)):
            np.testing.assert_allclose(
                o, r, rtol=rtol, atol=atol,
                err_msg="output %d disagrees between %s and %s"
                        % (i, ref_b, b))
        for n in ref_grads:
            np.testing.assert_allclose(
                grads[n], ref_grads[n], rtol=rtol, atol=atol,
                err_msg="grad %r disagrees between %s and %s"
                        % (n, ref_b, b))
    return results


# ---------------------------------------------------------------------------
# tiny attention LM — the shared decode-workload fixture
# ---------------------------------------------------------------------------

def tiny_attention_lm(vocab=32, dim=16, seed=0, dtype="float32"):
    """A single-head attention language model sized for CPU CI — the
    shared fixture behind the paged-decode tests and
    ``ci/decode_smoke.py``.

    Returns ``(params, step_fn, prefill_fn, token_spec, input_spec)``
    matching the :class:`mxnet_tpu.serve.DecodeEngine` contract:

    * ``step_fn(params, view, {"tok": (S,)}, pos)`` embeds the token,
      writes its K/V **exactly at position pos**, attends causally
      (everything past ``pos`` masked to -1e30 — positions beyond the
      cursor hold co-tenant garbage by design) and emits the greedy
      argmax next token, ``(S,) int32``;
    * ``prefill_fn`` computes K/V for a whole prompt prefix in one
      matrix product (row-wise bit-identical to the per-step path).

    The greedy emission makes every decode path — dense solo, paged
    batched ticks, speculative verify — comparable bit-for-bit on the
    token stream.
    """
    import jax
    import jax.numpy as jnp

    jdt = jnp.dtype(dtype)
    rs = np.random.RandomState(seed)
    params = {
        name: jnp.asarray(rs.randn(*shape).astype(np.float32) * 0.3,
                          jdt)
        for name, shape in (("E", (vocab, dim)), ("Wq", (dim, dim)),
                            ("Wk", (dim, dim)), ("Wv", (dim, dim)),
                            ("Wo", (dim, vocab)))}
    scale = jnp.asarray(1.0 / np.sqrt(dim), jdt)

    def step_fn(p, view, inputs, pos):
        tok = inputs["tok"]                    # (S,) int32
        x = p["E"][tok]                        # (S, D)
        q = x @ p["Wq"]
        k = x @ p["Wk"]
        v = x @ p["Wv"]
        idx = jnp.arange(view["k"].shape[0])
        nk = view["k"].at[idx, pos].set(k)     # write AT pos only
        nv = view["v"].at[idx, pos].set(v)
        seq = view["k"].shape[1]
        scores = jnp.einsum("sd,sld->sl", q, nk) * scale
        mask = jnp.arange(seq)[None, :] <= pos[:, None]
        scores = jnp.where(mask, scores, jnp.asarray(-1e30, jdt))
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("sl,sld->sd", probs, nv)
        logits = ctx @ p["Wo"]
        out = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return out, {"k": nk, "v": nv}

    def prefill_fn(p, inputs, length):
        toks = inputs["tok"][0]                # (Lr,)
        x = p["E"][toks]
        return {"k": (x @ p["Wk"])[None], "v": (x @ p["Wv"])[None]}

    token_spec = {"k": jax.ShapeDtypeStruct((dim,), jdt),
                  "v": jax.ShapeDtypeStruct((dim,), jdt)}
    input_spec = {"tok": jax.ShapeDtypeStruct((), jnp.int32)}
    return params, step_fn, prefill_fn, token_spec, input_spec


def dense_decode_reference(params, step_fn, prompt, n_new, padded_len,
                           dim, dtype="float32", input_name="tok",
                           cache_keys=("k", "v")):
    """Solo dense-cache greedy decode — THE bit-equality oracle for
    the paged decode path (tests/test_decode.py, ci/decode_smoke.py):
    the same ``step_fn`` over ONE dense worst-case cache
    ``(1, padded_len, dim)``, one dispatch per token.  The prompt is
    fed token by token at ``pos = t``; the LAST prompt token's output
    is the first generated token (matching the engine's
    prefill-prefix + first-tick convention).  Returns the generated
    token stream as a list of ints."""
    import jax
    import jax.numpy as jnp

    jdt = jnp.dtype(dtype)
    view = {k: jnp.zeros((1, padded_len, dim), jdt)
            for k in cache_keys}
    stepped = jax.jit(step_fn)
    cur, t = None, 0
    for tok in prompt:
        out, view = stepped(
            params, view, {input_name: jnp.asarray([tok], jnp.int32)},
            jnp.asarray([t], jnp.int32))
        t += 1
        cur = int(out[0])
    stream = []
    for _ in range(int(n_new)):
        stream.append(cur)
        if len(stream) >= int(n_new):
            break
        out, view = stepped(
            params, view, {input_name: jnp.asarray([cur], jnp.int32)},
            jnp.asarray([t], jnp.int32))
        t += 1
        cur = int(out[0])
    return stream
