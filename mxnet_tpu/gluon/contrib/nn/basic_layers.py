"""Contrib layers (reference: gluon/contrib/nn/basic_layers.py:29-208 —
Concurrent, HybridConcurrent, Identity, SparseEmbedding, SyncBatchNorm)."""

from __future__ import annotations

from ... import nn
from ...block import Block, HybridBlock
from .... import ndarray as nd


class Concurrent(nn.Sequential):
    """Run children on the same input, concat outputs along *axis*."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def forward(self, x):
        out = [blk(x) for blk in self._children.values()]
        return nd.concat(*out, dim=self.axis)


class HybridConcurrent(nn.HybridSequential):
    """Hybridizable :class:`Concurrent`."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def hybrid_forward(self, F, x):
        out = [blk(x) for blk in self._children.values()]
        return F.concat(*out, dim=self.axis)


class Identity(HybridBlock):
    """Pass-through (useful as a Concurrent branch)."""

    def hybrid_forward(self, F, x):
        return x


class SparseEmbedding(Block):
    """Embedding whose gradient is row_sparse (reference:
    basic_layers.py:116 — sparse_grad Embedding for kvstore
    row_sparse_pull training).  Forward is a row gather; the backward
    tape records a RowSparseNDArray gradient holding only touched rows."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim}
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer, stype="row_sparse",
                grad_stype="row_sparse")

    def forward(self, x):
        weight = self.weight.row_sparse_data(x)
        return nd.Embedding(x, weight, **self._kwargs,
                            sparse_grad=True)

    def __repr__(self):
        return "SparseEmbedding({input_dim} -> {output_dim})".format(
            **self._kwargs)


class SyncBatchNorm(nn.BatchNorm):
    """Cross-device BatchNorm (reference: basic_layers.py:163 +
    src/operator/contrib/sync_batch_norm.cc).

    The reference synchronizes moments with a key-based global barrier
    across GPU workers.  On TPU the equivalent is a ``psum`` over the
    data-parallel mesh axis *inside* the compiled step — which is what
    the ``_contrib_SyncBatchNorm`` operator emits when an axis name is
    bound (ops/spatial.py).  Outside a pjit/shard_map context it reduces
    over the local batch only, which is identical semantics on one chip.
    """

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, **kwargs):
        super().__init__(axis=1, momentum=momentum, epsilon=epsilon,
                         in_channels=in_channels, **kwargs)
        self._num_devices = num_devices


class MultiHeadAttention(HybridBlock):
    """Multi-head scaled-dot-product attention over the framework's
    flash-attention operator.

    The reference predates Transformers (its transformer.cc contrib op
    is just div_sqrt_dim); this block is the TPU-native user surface
    for SURVEY §5.7 long context: q/k/v/out projections around
    ``contrib.DotProductAttention``, which lowers to the Pallas flash
    kernel on TPU and the chunked-scan path elsewhere — O(S*block)
    activation memory either way.

    Inputs/outputs are (batch, seq, units); ``num_heads`` must divide
    ``units``.  With one argument, self-attention; with three,
    cross-attention (query, key, value).
    """

    def __init__(self, units, num_heads, causal=False, use_bias=True,
                 **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError("units (%d) must be divisible by "
                             "num_heads (%d)" % (units, num_heads))
        self._units = units
        self._num_heads = num_heads
        self._causal = causal
        with self.name_scope():
            self.proj_query = nn.Dense(units, use_bias=use_bias,
                                       flatten=False, prefix="query_")
            self.proj_key = nn.Dense(units, use_bias=use_bias,
                                     flatten=False, prefix="key_")
            self.proj_value = nn.Dense(units, use_bias=use_bias,
                                       flatten=False, prefix="value_")
            self.proj_out = nn.Dense(units, use_bias=use_bias,
                                     flatten=False, prefix="out_")

    def _split(self, F, x):
        # (B, S, U) -> (B, H, S, U/H)
        x = F.Reshape(x, shape=(0, 0, self._num_heads, -1))
        return F.transpose(x, axes=(0, 2, 1, 3))

    def hybrid_forward(self, F, query, key=None, value=None):
        key = query if key is None else key
        value = key if value is None else value
        q = self._split(F, self.proj_query(query))
        k = self._split(F, self.proj_key(key))
        v = self._split(F, self.proj_value(value))
        att = F.contrib.DotProductAttention(q, k, v,
                                            causal=self._causal)
        # (B, H, S, d) -> (B, S, U)
        att = F.transpose(att, axes=(0, 2, 1, 3))
        att = F.Reshape(att, shape=(0, 0, -1))
        return self.proj_out(att)

    def __repr__(self):
        return "MultiHeadAttention(units=%d, heads=%d, causal=%s)" % (
            self._units, self._num_heads, self._causal)


class MoEFFN(HybridBlock):
    """Top-1 capacity-routed mixture-of-experts feed-forward layer over
    the ``_contrib_MoEFFN`` op (GShard einsum formulation).

    The reference has no MoE; this is the expert-parallel TPU extension
    at the USER level: dispatch/combine are static-shape einsums, so a
    ``ParallelTrainer(param_specs={r"expert_w": P("ep", None, None)})``
    shards the expert weights (and their optimizer state) over an
    ``ep`` mesh axis and XLA's SPMD partitioner inserts the token
    all-to-alls inside the compiled step — the trainer-level peer of
    ``parallel.moe_apply``'s explicit shard_map dispatch.

    Input/output: (batch, in_units) tokens (flatten sequences first).
    """

    def __init__(self, in_units, hidden, num_experts,
                 capacity_factor=1.0, act_type="relu", **kwargs):
        super().__init__(**kwargs)
        self._cf = float(capacity_factor)
        self._act = act_type
        with self.name_scope():
            self.gate_weight = self.params.get(
                "gate_weight", shape=(in_units, num_experts))
            self.expert_w1 = self.params.get(
                "expert_w1", shape=(num_experts, in_units, hidden))
            self.expert_w2 = self.params.get(
                "expert_w2", shape=(num_experts, hidden, in_units))

    def hybrid_forward(self, F, x, gate_weight, expert_w1, expert_w2):
        return F._contrib_MoEFFN(x, gate_weight, expert_w1, expert_w2,
                                 capacity_factor=self._cf,
                                 act_type=self._act)


class GatedMLP(HybridBlock):
    """Gated (SwiGLU) feed-forward ``W2(silu(W1 x) * W3 x)`` without
    biases, over the ``_contrib_GatedMLP`` op."""

    def __init__(self, units, hidden, weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.w1 = self.params.get("w1_weight", shape=(hidden, units),
                                      init=weight_initializer)
            self.w3 = self.params.get("w3_weight", shape=(hidden, units),
                                      init=weight_initializer)
            self.w2 = self.params.get("w2_weight", shape=(units, hidden),
                                      init=weight_initializer)

    def hybrid_forward(self, F, x, w1, w3, w2):
        return F.contrib.GatedMLP(x, w1, w3, w2)


class SharedExperts(GatedMLP):
    """The shared experts beside a routed layer: a `GatedMLP` over the
    ``_contrib_SharedExperts`` op, whose device time reads under scope
    ``mx.moe.shared``."""

    def hybrid_forward(self, F, x, w1, w3, w2):
        return F.contrib.SharedExperts(x, w1, w3, w2)


class GatedShortConv(HybridBlock):
    """The gated short causal convolution operator on (batch, seq,
    units): an input projection to three streams, a depthwise causal
    convolution of *kernel* taps over the product of two of them, gated
    by the third, and an output projection; no biases.  Over the
    ``_contrib_GatedShortConv`` op."""

    def __init__(self, units, kernel=3, weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_weight = self.params.get(
                "in_weight", shape=(3 * units, units),
                init=weight_initializer)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(units, kernel),
                init=weight_initializer)
            self.out_weight = self.params.get(
                "out_weight", shape=(units, units), init=weight_initializer)

    def hybrid_forward(self, F, x, in_weight, conv_weight, out_weight):
        return F.contrib.GatedShortConv(x, in_weight, conv_weight,
                                        out_weight)


class GatedDeltaNet(HybridBlock):
    """A linear-attention operator on (batch, seq, units): the gated delta
    rule (arXiv:2412.06464; with *allow_neg_eigval* the write strength in
    (0, 2) of arXiv:2411.12537) in the block form whose published keys are
    ``linear_num_key_heads``, ``linear_key_head_dim``,
    ``linear_value_head_dim``, ``linear_conv_kernel_dim`` and
    ``linear_allow_neg_eigval``.  *num_heads* heads carry a float32 state of
    *key_dim* x *value_dim* each along the sequence; no biases but the
    decay's:

    - six products of the input: q and k (*num_heads* x *key_dim* each) and v
      (*num_heads* x *value_dim*) as the row blocks of one matrix, the
      output's gate z (as wide as v), and one number a head and token each
      for the decay and for the write strength;
    - q, k and v through one depthwise causal convolution of *conv_kernel*
      taps over their concatenated channels, then silu; q and k L2-normed
      by head, q scaled by ``key_dim ** -0.5`` (``contrib.ShortConvHeads``:
      the kernels ``mx_gdnconv_fwd`` and ``mx_gdnconv_bwd``, one pass over
      the channels each way, where the program is lowered for the TPU on
      one device at widths of whole groups of ``lcm(key_dim, 128)``
      channels and a sequence of whole pieces of 64 rows; the same
      arithmetic in `jax.numpy` at every other input; span
      ``mx.gdnconv.plan`` says which);
    - ``g = -exp(A_log) softplus(a + dt_bias)`` (float32) and ``b =
      sigmoid(.)``, doubled with *allow_neg_eigval*
      (``contrib.DeltaRuleGates``);
    - the rule itself, ``contrib.GatedDeltaRule``, chunkwise in the op's own
      chunks of 64 tokens (the sequence has to be whole chunks);
    - ``rms(o, gamma) * silu(z)`` by head with one *value_dim*-wide gamma
      (``contrib.GatedRMSNorm``), and the output product.

    Device scopes ``mx.gdn.project`` (the six products), ``mx.gdn.conv``
    (convolution, silu and the two L2 norms, on the TPU the kernels
    ``mx_gdnconv_fwd`` and ``mx_gdnconv_bwd``; the gates' activations),
    ``mx.gdn.scan`` (the rule, forward and backward: on the TPU the kernels
    ``mx_gdn_fwd`` and ``mx_gdn_bwd`` with the moves to head-major and back
    around them) and ``mx.gdn.out`` (the gated norm and the output product);
    spans ``mx.gdn.plan`` and ``mx.gdnconv.plan`` a traced call, with the
    rule's and the convolution's ``path``."""

    def __init__(self, units, num_heads, key_dim, value_dim, conv_kernel=4,
                 allow_neg_eigval=False, epsilon=1e-6,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._units, self._heads = units, int(num_heads)
        self._dk, self._dv = int(key_dim), int(value_dim)
        self._eps = float(epsilon)
        self._neg = bool(allow_neg_eigval)
        keys, values = self._heads * self._dk, self._heads * self._dv
        self._channels = 2 * keys + values
        with self.name_scope():
            def weight(name, rows, cols):
                return self.params.get(name, shape=(rows, cols),
                                       init=weight_initializer)
            self.qkv_weight = weight("qkv_weight", self._channels, units)
            self.conv_weight = weight("conv_weight", self._channels,
                                      conv_kernel)
            self.decay_weight = weight("decay_weight", self._heads, units)
            self.beta_weight = weight("beta_weight", self._heads, units)
            self.a_log = self.params.get("a_log", shape=(self._heads,),
                                         init="zeros")
            self.dt_bias = self.params.get("dt_bias", shape=(self._heads,),
                                           init="zeros")
            self.gate_weight = weight("gate_weight", values, units)
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(self._dv,), init="ones")
            self.out_weight = weight("out_weight", units, values)

    def hybrid_forward(self, F, x, qkv_weight, conv_weight, decay_weight,
                       beta_weight, a_log, dt_bias, gate_weight, norm_gamma,
                       out_weight):
        from .... import symbol

        def product(w, n):
            return F.FullyConnected(x, w, no_bias=True, flatten=False,
                                    num_hidden=n)

        with symbol.AttrScope(__scope__="mx.gdn.project"):
            qkv = product(qkv_weight, self._channels)
            z = product(gate_weight, self._heads * self._dv)
            a = product(decay_weight, self._heads)
            b = product(beta_weight, self._heads)
        with symbol.AttrScope(__scope__="mx.gdn.conv"):
            heads = F.contrib.ShortConvHeads(
                qkv, conv_weight, num_heads=self._heads, key_dim=self._dk,
                eps=self._eps)
            gates = F.contrib.DeltaRuleGates(a, b, a_log, dt_bias,
                                             allow_neg_eigval=self._neg)
        with symbol.AttrScope(__scope__="mx.gdn.scan"):
            o = F.contrib.GatedDeltaRule(heads[0], heads[1], heads[2],
                                         gates[0], gates[1])
        with symbol.AttrScope(__scope__="mx.gdn.out"):
            return F.FullyConnected(
                F.contrib.GatedRMSNorm(o, z, norm_gamma, eps=self._eps),
                out_weight, no_bias=True, flatten=False,
                num_hidden=self._units)


class StateSpaceMixer(HybridBlock):
    """A state-space layer's mixer (Mamba-2, arXiv:2405.21060, as the
    ``bamba`` / ``granitemoehybrid`` models publish it): a float32 state of
    *head_dim* x *state* a head carried along the sequence under a scalar
    decay a head and token, in place of attention over it.  For ``x (batch,
    seq, units)`` with ``I = num_heads * head_dim``:

    - one product of the input, the row blocks of one stored matrix: the
      output's gate z (``I``), the channels that go through the convolution
      (x: ``I``, then the input and output maps B and C of *groups* groups,
      ``groups * state`` each) and one step size a head;
    - those channels through one depthwise causal convolution of
      *conv_kernel* taps with a bias, then silu (``contrib.ShortConvSilu``);
    - ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` in float32
      (``contrib.StateSpaceGates``);
    - the recurrence itself, ``contrib.StateSpaceScan``, chunkwise in chunks
      of *chunk* tokens (the sequence has to be whole chunks), with the skip
      ``D x``;
    - ``rms(y * silu(z), gamma)``, the gate in front of the norm and the
      norm over a group's whole width (``contrib.GatedRMSNorm`` with
      ``gate_first``), and the output product.

    No biases but the convolution's.  Device scopes ``mx.ssm.project`` (the
    product and its split), ``mx.ssm.conv`` (convolution, bias and silu, the
    gates' activations), ``mx.ssm.scan`` (the recurrence, forward and
    backward) and ``mx.ssm.out`` (the gated norm and the output product);
    spans ``mx.ssm.plan`` and ``mx.ssmconv.plan`` a traced call."""

    def __init__(self, units, num_heads, head_dim, state, groups=1,
                 conv_kernel=4, chunk=256, epsilon=1e-5,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % groups:
            raise ValueError("%d heads do not make %d groups"
                             % (num_heads, groups))
        self._units, self._heads = units, int(num_heads)
        self._groups, self._state = int(groups), int(state)
        self._chunk, self._eps = int(chunk), float(epsilon)
        self._inner = self._heads * int(head_dim)
        self._conv = self._inner + 2 * self._groups * self._state
        self._rows = self._inner + self._conv + self._heads
        with self.name_scope():
            def vector(name, size, init):
                return self.params.get(name, shape=(size,), init=init)
            self.in_weight = self.params.get(
                "in_weight", shape=(self._rows, units),
                init=weight_initializer)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(self._conv, conv_kernel),
                init=weight_initializer)
            self.conv_bias = vector("conv_bias", self._conv, "zeros")
            self.a_log = vector("a_log", self._heads, "zeros")
            self.dt_bias = vector("dt_bias", self._heads, "zeros")
            self.skip = vector("skip", self._heads, "ones")
            self.norm_gamma = vector("norm_gamma", self._inner, "ones")
            self.out_weight = self.params.get(
                "out_weight", shape=(units, self._inner),
                init=weight_initializer)

    def hybrid_forward(self, F, x, in_weight, conv_weight, conv_bias, a_log,
                       dt_bias, skip, norm_gamma, out_weight):
        from .... import symbol
        inner, maps = self._inner, self._groups * self._state

        def part(y, lo, hi):
            return F.slice_axis(y, axis=-1, begin=lo, end=hi)

        with symbol.AttrScope(__scope__="mx.ssm.project"):
            zxd = F.FullyConnected(x, in_weight, no_bias=True, flatten=False,
                                   num_hidden=self._rows)
            z = part(zxd, 0, inner)
            xbc = part(zxd, inner, inner + self._conv)
            dt = part(zxd, inner + self._conv, self._rows)
        with symbol.AttrScope(__scope__="mx.ssm.conv"):
            xbc = F.contrib.ShortConvSilu(xbc, conv_weight, conv_bias)
            gates = F.contrib.StateSpaceGates(dt, a_log, dt_bias)
            by_head = F.Reshape(part(xbc, 0, inner),
                                shape=(0, 0, self._heads, -1))
            b, c = (F.Reshape(part(xbc, lo, lo + maps),
                              shape=(0, 0, self._groups, -1))
                    for lo in (inner, inner + maps))
        with symbol.AttrScope(__scope__="mx.ssm.scan"):
            y = F.contrib.StateSpaceScan(by_head, gates[0], gates[1], b, c,
                                         skip, chunk=self._chunk)
        with symbol.AttrScope(__scope__="mx.ssm.out"):
            y = F.Reshape(y, shape=(0, 0, self._groups, -1))
            return F.FullyConnected(
                F.contrib.GatedRMSNorm(y, z, norm_gamma, eps=self._eps,
                                       gate_first=True),
                out_weight, no_bias=True, flatten=False,
                num_hidden=self._units)


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention with fewer key/value heads than query
    heads, RMS norm over each query and key head, and rotary positions;
    no biases.  The q and the k product each go through
    ``contrib.HeadNormRotary``: the norm, the rotation and the move to
    ``(batch, heads, seq, d)`` in one pass over the data each way (the
    kernels ``mx_headrope_fwd`` and ``mx_headrope_bwd``) where the
    program is lowered for the TPU at heads of whole 128-lane tiles and a
    sequence of whole tiles of 256 on one device, and ``contrib.RMSNorm``
    then ``contrib.RotaryEmbedding``'s arithmetic at every other input;
    span ``mx.headrope.plan`` says which.  The key/value heads are repeated
    to the query heads in front of ``contrib.DotProductAttention`` (the
    flash kernels take equal head counts).

    With *diffusion_block* the attention is not causal: the sequence is a
    clean copy then a noised copy of half its length each, and the mask
    is the block-diffusion one in blocks of that many positions
    (`ops/attention.py` `BlockDiffusion`).  An optional second input,
    ``positions`` ``(1, batch, seq)``, gives the rotary positions (both
    copies of a token stand at the same one); without it they are ``0 ..
    seq - 1``.  In a compiled graph the projections then lie under device
    scope ``mx.bd.project`` and the attention under ``mx.bd.attention``.

    With *window* a query sees that many keys, its own the last
    (`ops/attention.py` `Window`: the flash kernels' loops skip the tiles the
    window leaves dark on both sides).  With *gate* one more projection to
    ``num_heads`` numbers a token goes through a sigmoid and multiplies each
    head's output in front of the output projection (the headwise output
    gate of arXiv:2505.06708).  *rope* is one layer kind's entry of a
    published ``rope_parameters`` mapping (`ops/lm_blocks.py`
    `rope_frequencies`: ``rope_theta``, ``partial_rotary_factor``, YaRN's
    keys) in *rope_theta*'s place; a part of a head turned, or YaRN's
    frequencies, take the same kernels on the same terms (a part by two
    rolls and a third table; ``mx.headrope.plan`` carries ``rotary_dim``).
    Device scopes ``mx.swa.project``,
    ``mx.swa.attention`` and ``mx.swa.out`` for a layer with a window,
    ``mx.gqa.project``, ``mx.gqa.attention`` and ``mx.gqa.out`` for one
    with a gate and none.  Without the three this is the block it was.

    Two departures a layer may ask for beside them.  *qk_norm* ``"width"``
    puts one RMS norm over the whole width of the q product and one over the
    k product's (scales ``(num_heads * head_dim,)`` and ``(num_kv_heads *
    head_dim,)``: the norm runs over all the heads' numbers at once,
    arXiv:2501.00656 section 3) in place of ``"head"``'s norm over each
    head.  A *rope* entry whose ``rope_theta`` is null switches the rotary
    positions off: q and k go to the attention normed and not turned (a
    model whose other layers carry the order).  With either, the q and k
    products do not take the head-rope kernels (``mx.headrope.plan`` says
    ``path`` ``xla`` and why), and the layer takes the scopes ``mx.gqa.*``
    as a gated one does.  *qk_norm* None leaves q and k as projected: no
    norm parameters are created, and with a null ``rope_theta`` beside it
    (the only way it is built: a norm-less q and k with rotary positions has
    no caller) the two go to the attention by a reshape and a transpose, as
    v does.  *scale* is the softmax's scale in place of ``head_dim ** -0.5``
    (a model that publishes an ``attention_multiplier``); a layer with
    either takes the scopes ``mx.gqa.*`` too."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim=None,
                 rope_theta=10000.0, epsilon=1e-5, weight_initializer=None,
                 diffusion_block=None, window=None, gate=False, rope=None,
                 qk_norm="head", scale=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("num_heads (%d) must be a multiple of "
                             "num_kv_heads (%d)" % (num_heads, num_kv_heads))
        if window and diffusion_block:
            raise ValueError("a window goes with a causal mask, not with "
                             "the block-diffusion one")
        if qk_norm not in ("head", "width", None):
            raise ValueError("qk_norm %r is neither \"head\" (a norm over "
                             "each head) nor \"width\" (one over all of "
                             "them)" % (qk_norm,))
        head_dim = head_dim or units // num_heads
        self._scale = head_dim ** -0.5 if scale is None else float(scale)
        self._units = units
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._head_dim, self._theta = head_dim, float(rope_theta)
        self._eps = epsilon
        self._rotary = {"theta": self._theta}
        # a published entry whose rope_theta is null: no rotary positions
        unturned = bool(rope) and "rope_theta" in rope \
            and rope["rope_theta"] is None
        if unturned:
            self._rotary = {"rotary": False}
        elif rope:
            from ....ops.lm_blocks import rope_frequencies
            self._rotary = rope_frequencies(rope, head_dim)
        if qk_norm == "width":
            self._rotary["norm_over"] = "width"
        if qk_norm is None and not unturned:
            raise ValueError("q and k with no norm and rotary positions are "
                             "not built: qk_norm None goes with a rope "
                             "entry whose rope_theta is null")
        departs = unturned or qk_norm != "head" or scale is not None
        self._mask = {"causal": True} if not diffusion_block else {
            "mask": "block_diffusion", "mask_block": int(diffusion_block)}
        if window:
            self._mask["window"] = int(window)
        # the block's nodes as named groups of the compiled graph
        scope = "mx.bd" if diffusion_block else "mx.swa" if window \
            else "mx.gqa" if gate or departs else None
        self._group = {"__scope__": scope + ".project"} if scope else {}
        # (under the block-diffusion mask the op names its own scope)
        self._after = {} if scope in (None, "mx.bd") else {
            part: {"__scope__": "%s.%s" % (scope, part)}
            for part in ("attention", "out")}
        with self.name_scope():
            def weight(name, rows, cols):
                return self.params.get(name, shape=(rows, cols),
                                       init=weight_initializer)
            self.q_weight = weight("query_weight", num_heads * head_dim,
                                   units)
            self.k_weight = weight("key_weight", num_kv_heads * head_dim,
                                   units)
            self.v_weight = weight("value_weight", num_kv_heads * head_dim,
                                   units)
            self.out_weight = weight("out_weight", units,
                                     num_heads * head_dim)
            wide = qk_norm == "width"
            if qk_norm:
                self.q_gamma = self.params.get(
                    "query_norm_gamma", init="ones",
                    shape=(num_heads * head_dim if wide else head_dim,))
                self.k_gamma = self.params.get(
                    "key_norm_gamma", init="ones",
                    shape=(num_kv_heads * head_dim if wide else head_dim,))
            if gate:
                self.gate_weight = weight("gate_weight", num_heads, units)

    def hybrid_forward(self, F, x, positions=None, q_weight=None,
                       k_weight=None, v_weight=None, out_weight=None,
                       q_gamma=None, k_gamma=None, gate_weight=None):
        def heads(w, n, gamma=None):
            # (B, S, U) -> (B, S, n * d) -> (B, n, S, d); q and k normed
            # over d and turned on the way
            h = F.FullyConnected(x, w, no_bias=True, flatten=False,
                                 num_hidden=n * self._head_dim)
            if gamma is None:
                return F.transpose(F.Reshape(h, shape=(0, 0, n, -1)),
                                   axes=(0, 2, 1, 3))
            where = () if positions is None else (positions,)
            return F.contrib.HeadNormRotary(
                h, gamma, *where, num_heads=n, eps=self._eps,
                use_positions=bool(where), **self._rotary)

        from .... import symbol
        with symbol.AttrScope(**self._group):
            q = heads(q_weight, self._heads, q_gamma)
            k = heads(k_weight, self._kv_heads, k_gamma)
            v = heads(v_weight, self._kv_heads)
            group = self._heads // self._kv_heads
            if group > 1:
                k = F.repeat(k, repeats=group, axis=1)
                v = F.repeat(v, repeats=group, axis=1)
            if gate_weight is not None:
                # (B, S, H) -> (B, H, S, 1): one number a head and token
                gate = F.expand_dims(F.transpose(F.sigmoid(F.FullyConnected(
                    x, gate_weight, no_bias=True, flatten=False,
                    num_hidden=self._heads)), axes=(0, 2, 1)), axis=3)
        with symbol.AttrScope(**self._after.get("attention", {})):
            att = F.contrib.DotProductAttention(
                q, k, v, sm_scale=self._scale, **self._mask)
        with symbol.AttrScope(**self._after.get("out", {})):
            if gate_weight is not None:
                att = F.broadcast_mul(att, gate)
            att = F.Reshape(F.transpose(att, axes=(0, 2, 1, 3)),
                            shape=(0, 0, -1))
            return F.FullyConnected(att, out_weight, no_bias=True,
                                    flatten=False, num_hidden=self._units)


class SparseAttention(HybridBlock):
    """Causal grouped-query attention over the keys a learned indexer
    chooses for each query (learned sparse attention, as DeepSeek-V3.2-Exp
    publishes it), over the ``_contrib_SparseAttention`` op: *num_heads*
    query heads over *num_kv_heads* key/value heads with RMS norm over each
    query and key head and rotary positions, as `GroupedQueryAttention`;
    an indexer of *index_heads* heads of *index_head_dim* over one key
    head scores every causal key, a query sees the *topk* best (all of
    them where there are no more), and the indexer's three matrices are
    trained by an alignment term alone.  Returns ``(output, term)``: the
    term, shape ``(1,)``, is for the objective to add
    (`gluon.model_zoo.decoder` `AlignedLoss`); it is the only path to the
    indexer, and the output's gradient reaches everything else.
    *mrope_section* deals the rotary frequencies to the axes of a
    ``positions`` input ``(axes, batch, seq)``; without that input the
    positions are a text's.  No biases."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim=None,
                 index_heads=16, index_head_dim=64, topk=2048,
                 rope_theta=10000.0, mrope_section=(), epsilon=1e-6,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError("num_heads (%d) must be a multiple of "
                             "num_kv_heads (%d)" % (num_heads, num_kv_heads))
        head_dim = head_dim or units // num_heads
        self._attrs = {
            "num_heads": int(num_heads), "num_kv_heads": int(num_kv_heads),
            "index_heads": int(index_heads), "topk": int(topk),
            "rope_theta": float(rope_theta),
            "mrope_section": tuple(int(n) for n in mrope_section),
            "eps": float(epsilon)}
        with self.name_scope():
            def weight(name, rows, cols):
                return self.params.get(name, shape=(rows, cols),
                                       init=weight_initializer)
            self.q_weight = weight("query_weight", num_heads * head_dim,
                                   units)
            self.k_weight = weight("key_weight", num_kv_heads * head_dim,
                                   units)
            self.v_weight = weight("value_weight", num_kv_heads * head_dim,
                                   units)
            self.out_weight = weight("out_weight", units,
                                     num_heads * head_dim)
            self.q_gamma = self.params.get(
                "query_norm_gamma", shape=(head_dim,), init="ones")
            self.k_gamma = self.params.get(
                "key_norm_gamma", shape=(head_dim,), init="ones")
            self.index_q_weight = weight(
                "index_query_weight", index_heads * index_head_dim, units)
            self.index_k_weight = weight("index_key_weight", index_head_dim,
                                         units)
            self.index_w_weight = weight("index_head_weight", index_heads,
                                         units)

    def hybrid_forward(self, F, x, positions=None, q_weight=None,
                       k_weight=None, v_weight=None, out_weight=None,
                       q_gamma=None, k_gamma=None, index_q_weight=None,
                       index_k_weight=None, index_w_weight=None):
        weights = (q_weight, k_weight, v_weight, out_weight, q_gamma,
                   k_gamma, index_q_weight, index_k_weight, index_w_weight)
        if positions is None:
            out = F.contrib.SparseAttention(x, *weights, **self._attrs)
        else:
            out = F.contrib.SparseAttention(x, *weights, positions,
                                            use_positions=True,
                                            **self._attrs)
        return out[0], out[1]


class LatentAttention(HybridBlock):
    """Causal multi-head latent attention, the expanded form, over the
    ``_contrib_LatentAttention`` op: queries of *qk_nope_head_dim* +
    *qk_rope_head_dim* a head, keys and values expanded from one latent
    of *kv_lora_rank* (RMS-normed) with one rotary key of
    *qk_rope_head_dim* for all heads, values *v_head_dim* wide; no
    biases, no query compression."""

    def __init__(self, units, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta=10000.0,
                 rope_interleave=True, epsilon=1e-6,
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        self._attrs = {
            "num_heads": int(num_heads),
            "qk_nope_head_dim": int(qk_nope_head_dim),
            "qk_rope_head_dim": int(qk_rope_head_dim),
            "v_head_dim": int(v_head_dim), "rope_theta": float(rope_theta),
            "rope_interleave": bool(rope_interleave), "eps": float(epsilon)}
        qk = qk_nope_head_dim + qk_rope_head_dim
        with self.name_scope():
            def weight(name, rows, cols):
                return self.params.get(name, shape=(rows, cols),
                                       init=weight_initializer)
            self.q_weight = weight("query_weight", num_heads * qk, units)
            self.kv_a_weight = weight("kv_a_weight",
                                      kv_lora_rank + qk_rope_head_dim, units)
            self.kv_norm_gamma = self.params.get(
                "kv_norm_gamma", shape=(kv_lora_rank,), init="ones")
            self.kv_b_weight = weight(
                "kv_b_weight", num_heads * (qk_nope_head_dim + v_head_dim),
                kv_lora_rank)
            self.out_weight = weight("out_weight", units,
                                     num_heads * v_head_dim)

    def hybrid_forward(self, F, x, q_weight, kv_a_weight, kv_norm_gamma,
                       kv_b_weight, out_weight):
        return F.contrib.LatentAttention(
            x, q_weight, kv_a_weight, kv_norm_gamma, kv_b_weight, out_weight,
            **self._attrs)


class RoutedExperts(HybridBlock):
    """Dropless top-k routed gated experts, one chip's share of the
    layer, over the ``_contrib_RoutedExperts`` op: the router scores all
    *num_experts*, this block holds *experts_held* of them from
    *first_expert* on and computes their part of the result.
    *expert_bias* (one number an expert, added to the scores for the
    choice alone) is an attribute of the layer, not a parameter."""

    def __init__(self, units, hidden, num_experts, num_experts_per_tok,
                 experts_held=None, first_expert=0, expert_bias=None,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 weight_initializer=None, scoring_func="sigmoid", **kwargs):
        super().__init__(**kwargs)
        held = num_experts if experts_held is None else experts_held
        if first_expert < 0 or first_expert + held > num_experts:
            raise ValueError("experts %d..%d are not among the router's %d"
                             % (first_expert, first_expert + held - 1,
                                num_experts))
        if expert_bias is not None and len(expert_bias) != num_experts:
            raise ValueError("expert_bias needs one number for each of the "
                             "router's %d experts" % num_experts)
        self._attrs = {
            "expert_bias": tuple(float(b) for b in expert_bias or ()),
            "num_experts_per_tok": int(num_experts_per_tok),
            "first_expert": int(first_expert),
            "norm_topk_prob": bool(norm_topk_prob),
            "routed_scaling_factor": float(routed_scaling_factor)}
        if scoring_func != "sigmoid":
            # ``softmax``: a softmax over all the experts, its top-k
            # renormalised, no bias and no scaling
            self._attrs["scoring_func"] = str(scoring_func)
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units),
                init=weight_initializer)
            self.w1 = self.params.get(
                "expert_w1", shape=(held, units, hidden),
                init=weight_initializer)
            self.w3 = self.params.get(
                "expert_w3", shape=(held, units, hidden),
                init=weight_initializer)
            self.w2 = self.params.get(
                "expert_w2", shape=(held, hidden, units),
                init=weight_initializer)

    def hybrid_forward(self, F, x, router_weight, w1, w3, w2):
        return F.contrib.RoutedExperts(x, router_weight, w1, w3, w2,
                                       **self._attrs)
