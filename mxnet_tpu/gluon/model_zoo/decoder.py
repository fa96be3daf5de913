"""A decoder-only language model built from a list of layer kinds: each
layer has an operator and a feed-forward, each with an RMS norm and added
to the residual.  The norm sits on the input (pre-norm, the default):

    h = h + operator(rms(h));  h = h + feed_forward(rms(h))

or, for the layer kinds that ``norm_place`` maps to ``"output"``, on what
the operator and the feed-forward give back (the reordered norm of
arXiv:2501.00656 section 3):

    h = h + rms(operator(h));  h = h + rms(feed_forward(h))

then a final RMS norm and a head.  The operator is one of eight kinds
(`OPERATOR_KINDS`):

- ``"conv"``: a gated short causal convolution;
- ``"full_attention"``: grouped-query attention with per-head RMS norm
  and rotary positions;
- ``"sliding_attention"``: the same block through a causal window of
  ``sliding_window`` keys, the query's own among them (`ops/attention.py`
  `Window`: the flash kernels skip the tiles the window leaves dark on both
  sides); device scopes ``mx.swa.project``, ``mx.swa.attention``,
  ``mx.swa.out``;
- ``"latent_attention"``: multi-head latent attention, the expanded form:
  keys and values from one low-rank latent, one rotary key for all heads,
  keys wider than values (``kv_lora_rank``, ``qk_nope_head_dim``,
  ``qk_rope_head_dim``, ``v_head_dim``, ``rope_interleave``);
- ``"sparse_attention"``: grouped-query attention over the keys a learned
  indexer chooses for each query (``index_heads`` heads of
  ``index_head_dim`` over one key head, the ``index_topk`` best causal keys
  a query), the indexer trained by an alignment term alone; rotary
  frequencies dealt to the axes of an optional ``positions`` input by
  ``mrope_section``.  A net with such layers returns ``(logits, term)``:
  ``alignment_weight`` times the mean of the layers' terms, which
  `AlignedLoss` adds to the objective beside a loss of the logits;
- ``"block_diffusion_attention"``: grouped-query attention as
  ``"full_attention"`` has it, under the block-diffusion mask in blocks of
  ``diffusion_block`` positions (`ops/attention.py` `BlockDiffusion`,
  BD3-LM, arXiv:2503.09573): the net's input is ``(B, 2L)`` ids, a clean
  copy of each sequence then a noised copy; both copies of token ``i`` stand
  at rotary position ``i``; a clean query sees the clean keys of its own and
  of earlier blocks, a noised one the clean keys of earlier blocks and the
  noised keys of its own block, forward and backward.  The final norm and
  the head read the noised half alone, so the net returns ``(B, L, vocab)``
  logits, and `BlockDiffusionLoss` is its objective: the cross-entropy of
  the clean token at each masked position (no shift), weighted by the
  label's second plane.  Device scopes ``mx.bd.project`` and
  ``mx.bd.attention``;
- ``"linear_attention"``: the gated delta rule (`contrib.nn.GatedDeltaNet`,
  `ops/delta_rule.py`): a float32 state a head carried along the sequence in
  chunks of 64 tokens in place of attention over it, behind one short causal
  convolution of q, k and v; its own widths come as the mapping ``linear``
  (``num_key_heads``, ``num_value_heads``, ``key_head_dim``,
  ``value_head_dim``, ``conv_kernel_dim``, ``allow_neg_eigval``: the
  published ``linear_*`` keys without the prefix).  The sequence has to be
  whole chunks.  Device scopes ``mx.gdn.project``, ``mx.gdn.conv``,
  ``mx.gdn.scan`` (two Mosaic kernels on the TPU, `jax.numpy` elsewhere:
  span ``mx.gdn.plan``) and ``mx.gdn.out``;
- ``"mamba"`` (the published word): a Mamba-2 state-space mixer
  (`contrib.nn.StateSpaceMixer`, `ops/state_space.py`): a float32 state of
  ``d_head x d_state`` a head under a scalar decay a head and token, in
  chunks of ``chunk_size`` tokens, behind one short causal convolution with
  a bias and in front of a norm whose gate comes first; its widths come as
  the mapping ``state_space`` (``n_heads``, ``d_head``, ``d_state``,
  ``n_groups``, ``d_conv``, ``chunk_size``: the published ``mamba_*`` keys
  without the prefix).  The sequence has to be whole chunks.  Device scopes
  ``mx.ssm.project``, ``mx.ssm.conv``, ``mx.ssm.scan`` (span
  ``mx.ssm.plan``) and ``mx.ssm.out``.

What is a property of the layer and not of the net: ``heads`` may be a
list, one count a layer (a model whose window layers have more query heads
than its full ones); ``rope_parameters`` maps a layer kind to its rotary
settings, shaped as the published key of that name (``{"full_attention":
{"rope_type": "yarn", "rope_theta": ..., "factor": ...,
"partial_rotary_factor": 0.5, ...}, "sliding_attention": {"rope_type":
"default", "rope_theta": ...}}``, `ops/lm_blocks.py` `rope_frequencies`), a
kind it does not name keeping ``rope_theta``, and an entry whose
``rope_theta`` is null switching the rotary positions of that kind off;
``attention_gate`` gives the
full_attention and sliding_attention layers a per-head sigmoid gate on the
attention's output (then under device scopes ``mx.gqa.*`` and
``mx.swa.*``); ``norm_place`` maps a layer kind to ``"input"`` or
``"output"`` (a kind it does not name is pre-normed); ``qk_norm`` maps a
layer kind to ``"head"`` (the default: an RMS norm over each query and key
head), ``"width"`` (one over all the heads' numbers at once) or None (none:
with a null ``rope_theta`` q and k go to the attention as projected);
``attention_scale`` is the softmax's scale in the full_attention and
sliding_attention layers in place of ``head_dim ** -0.5`` (a published
``attention_multiplier``).

Three multipliers of the net, each 1 by default and then absent from the
graph: ``residual_multiplier`` scales what the operator and the feed-forward
add to the residual (``h = h + m operator(rms(h))``),
``embedding_multiplier`` the embedding's rows as they enter, and the logits
are divided by ``logits_scaling``.

The feed-forward is a dense gated MLP in the first ``num_dense_layers``
layers and dropless top-k routed experts in the others, to which
``shared_hidden`` > 0 adds shared experts: one gated MLP of that width
that every token passes through (and every chip computes alike), under
device scope ``mx.moe.shared``.  The router scores with a sigmoid, or with
``scoring_func`` ``softmax`` with a softmax over all the experts whose
top-k is renormalised.  The head is the embedding itself
(``tied_head``, the default) or a matrix of its own.

These are the shapes of LiquidAI's LFM2 mixture-of-experts models
(``model_type`` ``lfm2_moe``: conv and full_attention layers, a tied head)
and of the ``deepseek_v3`` family (latent attention, shared experts, an
untied head), and of Kwai-Keye's ``KeyeVL2`` language model (sparse
attention, a softmax router, three-axis rotary positions), and of JetLM's
``sdar_moe`` models (block_diffusion_attention layers, a softmax router, an
untied head), and of poolside's ``laguna`` models (sliding_attention and
full_attention layers mixed, head counts by layer, an output gate, rotary
settings by layer kind, a sigmoid router with a shared expert), and of
Ai2's ``olmo_hybrid`` models (linear_attention and full_attention layers
mixed, the full ones with the norm on the output, a norm over the width of
q and k and no rotary positions, a dense feed-forward everywhere), and of
IBM's ``granitemoehybrid`` models (mamba and full_attention layers mixed,
the attention with no positions, no q/k norm and a scale of its own, the
three multipliers, a dense feed-forward everywhere, a tied head), whose
published ``config.json`` keys the arguments follow;
`benchmarks/models/lfm2_moe.py`, `benchmarks/models/deepseek_v3.py`,
`benchmarks/models/keye_vl2.py`, `benchmarks/models/sdar_moe.py`,
`benchmarks/models/laguna.py`, `benchmarks/models/olmo_hybrid.py` and
`benchmarks/models/granitemoehybrid.py` build one from such a file.

The routed layers hold ONE CHIP'S SHARE of their experts
(`gluon.contrib.nn.RoutedExperts`): ``experts_held`` of ``num_experts``
from ``first_expert`` on, with the router over all of them; likewise
``vocab`` may be the rows of the vocabulary held here.

Usage::

    net = get_decoder_lm(vocab=1024, dim=256, layer_types=["conv",
                         "full_attention"], num_dense_layers=1, ...)
    logits = net(tokens)         # (B, S) int -> (B, S, vocab)
"""

from __future__ import annotations

from ..block import HybridBlock
from .. import nn
from ..contrib.nn import (GatedDeltaNet, GatedMLP, GatedShortConv,
                          GroupedQueryAttention, LatentAttention,
                          RoutedExperts, SharedExperts, SparseAttention,
                          StateSpaceMixer)

__all__ = ["AlignedLoss", "BlockDiffusionLoss", "DecoderLayer", "DecoderLM",
           "get_decoder_lm", "OPERATOR_KINDS"]

OPERATOR_KINDS = ("conv", "full_attention", "latent_attention",
                  "sparse_attention", "block_diffusion_attention",
                  "sliding_attention", "linear_attention", "mamba")
NORM_PLACES = ("input", "output")


class SharedAndRouted(HybridBlock):
    """A routed feed-forward with shared experts: ``shared(x) +
    routed(x)``, the shared part one gated MLP every token passes
    through."""

    def __init__(self, routed, shared, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.shared = shared()
            self.routed = routed()

    def hybrid_forward(self, F, x):
        return self.shared(x) + self.routed(x)


class DecoderLayer(HybridBlock):
    """One layer: *operator* is built by kind (*latent* holds
    `LatentAttention`'s own widths, *sparse* `SparseAttention`'s, *linear*
    `GatedDeltaNet`'s, *state_space* `StateSpaceMixer`'s, *grouped* what
    `GroupedQueryAttention` takes beside its head counts: ``window``,
    ``gate``, ``rope``, ``qk_norm``, ``scale``), *feed_forward* is handed in.
    *norm_place* ``"input"`` norms what the operator and the feed-forward
    read (pre-norm), ``"output"`` what they give back; *residual_multiplier*
    scales what the two add to the residual.  A ``positions`` input goes to a
    sparse_attention or block_diffusion_attention operator and to no other;
    a sparse_attention layer returns ``(output, alignment term)``."""

    def __init__(self, dim, kind, feed_forward, heads, kv_heads, head_dim,
                 rope_theta, conv_kernel, eps, init, latent=None,
                 sparse=None, diffusion_block=None, grouped=None,
                 linear=None, norm_place="input", state_space=None,
                 residual_multiplier=1.0, **kwargs):
        super().__init__(**kwargs)
        self._residual = float(residual_multiplier)
        if kind not in OPERATOR_KINDS:
            raise ValueError(
                "layer kind %r is not one of %s (a gated short convolution, "
                "grouped-query attention, multi-head latent attention, "
                "learned sparse attention, grouped-query attention under "
                "the block-diffusion mask, grouped-query attention through "
                "a causal window, the gated delta rule's linear attention, a "
                "Mamba-2 state-space mixer)" % (kind, OPERATOR_KINDS))
        if norm_place not in NORM_PLACES:
            raise ValueError("a layer's norms sit on the %s or on the %s of "
                             "its operator and feed-forward, not %r"
                             % (NORM_PLACES + (norm_place,)))
        self._norm_output = norm_place == "output"
        grouped = dict(grouped or {})
        self._takes_positions = kind in ("sparse_attention",
                                         "block_diffusion_attention")
        self._returns_term = kind == "sparse_attention"
        with self.name_scope():
            self.operator_norm = nn.RMSNorm(dim, eps,
                                            prefix="operator_norm_")
            if kind == "conv":
                self.operator = GatedShortConv(
                    dim, conv_kernel, weight_initializer=init,
                    prefix="conv_")
            elif kind in ("full_attention", "sliding_attention"):
                if kind == "sliding_attention" and not grouped.get("window"):
                    raise ValueError("a sliding_attention layer needs "
                                     "sliding_window")
                self.operator = GroupedQueryAttention(
                    dim, heads, kv_heads, head_dim, rope_theta, eps,
                    weight_initializer=init, prefix="attn_", **grouped)
            elif kind == "block_diffusion_attention":
                if not diffusion_block:
                    raise ValueError("a block_diffusion_attention layer "
                                     "needs diffusion_block")
                self.operator = GroupedQueryAttention(
                    dim, heads, kv_heads, head_dim, rope_theta, eps,
                    weight_initializer=init, prefix="attn_",
                    diffusion_block=diffusion_block)
            elif kind == "linear_attention":
                if not linear:
                    raise ValueError(
                        "a linear_attention layer needs linear: "
                        "num_key_heads, num_value_heads, key_head_dim, "
                        "value_head_dim, conv_kernel_dim")
                if linear["num_key_heads"] != linear["num_value_heads"]:
                    raise ValueError(
                        "a linear_attention layer with %d key heads for %d "
                        "value heads is not built: one state a head"
                        % (linear["num_key_heads"],
                           linear["num_value_heads"]))
                self.operator = GatedDeltaNet(
                    dim, linear["num_value_heads"], linear["key_head_dim"],
                    linear["value_head_dim"],
                    conv_kernel=linear["conv_kernel_dim"],
                    allow_neg_eigval=linear.get("allow_neg_eigval", False),
                    epsilon=eps, weight_initializer=init, prefix="gdn_")
            elif kind == "mamba":
                if not state_space:
                    raise ValueError(
                        "a mamba layer needs state_space: n_heads, d_head, "
                        "d_state, n_groups, d_conv, chunk_size")
                self.operator = StateSpaceMixer(
                    dim, state_space["n_heads"], state_space["d_head"],
                    state_space["d_state"], state_space.get("n_groups", 1),
                    state_space.get("d_conv", 4),
                    state_space.get("chunk_size", 256), epsilon=eps,
                    weight_initializer=init, prefix="ssm_")
            elif kind == "sparse_attention":
                if not sparse:
                    raise ValueError(
                        "a sparse_attention layer needs index_heads, "
                        "index_head_dim and index_topk")
                self.operator = SparseAttention(
                    dim, heads, kv_heads, head_dim, rope_theta=rope_theta,
                    epsilon=eps, weight_initializer=init, prefix="dsa_",
                    **sparse)
            else:
                if not latent:
                    raise ValueError(
                        "a latent_attention layer needs kv_lora_rank, "
                        "qk_nope_head_dim, qk_rope_head_dim and v_head_dim")
                self.operator = LatentAttention(
                    dim, heads, rope_theta=rope_theta, epsilon=eps,
                    weight_initializer=init, prefix="mla_", **latent)
            self.ffn_norm = nn.RMSNorm(dim, eps, prefix="ffn_norm_")
            self.feed_forward = feed_forward()

    def hybrid_forward(self, F, x, positions=None):
        h = x if self._norm_output else self.operator_norm(x)
        out = self.operator(h, positions) \
            if self._takes_positions and positions is not None \
            else self.operator(h)
        if self._returns_term:
            out, term = out

        def add(x, y):
            return x + (y if self._residual == 1.0 else y * self._residual)

        if self._norm_output:
            x = add(x, self.operator_norm(out))
            x = add(x, self.ffn_norm(self.feed_forward(x)))
        else:
            x = add(x, out)
            x = add(x, self.feed_forward(self.ffn_norm(x)))
        return (x, term) if self._returns_term else x


class DecoderLM(HybridBlock):
    """Token embedding, the layers of *layer_types* (the first
    *num_dense_layers* with a dense gated MLP of width *dense_hidden*,
    the others with routed experts of width *expert_hidden* and, with
    *shared_hidden* > 0, shared experts beside them), final RMS norm, and
    logits against the embedding itself (*tied_head*) or against a head
    matrix of its own.  A second input, ``positions`` ``(axes, batch,
    seq)``, reaches the sparse_attention layers' rotary positions; with
    such layers the net returns ``(logits, term)``, *alignment_weight*
    times the mean of their alignment terms, shape ``(1,)``.  With
    block_diffusion_attention layers (blocks of *diffusion_block*) the input
    is ``(batch, 2 * seq)``, a clean copy then a noised copy, the positions
    are ``j mod seq`` where none are given, and the logits are the noised
    half's: ``(batch, seq, vocab)``.  *heads* is one count or a list of one
    a layer; *rope_parameters* maps a layer kind to its rotary settings (a
    published ``rope_parameters``); *sliding_window* is the
    sliding_attention layers' window and *attention_gate* the per-head
    output gate of those and of the full_attention layers; *linear* holds
    the linear_attention layers' widths; *norm_place* maps a layer kind to
    where its norms sit (``"input"`` or ``"output"``) and *qk_norm* a
    full_attention or sliding_attention kind to ``"head"``, ``"width"`` or
    None; *state_space* holds the mamba layers' widths; *attention_scale* is
    the softmax's scale of the full_attention and sliding_attention layers;
    *residual_multiplier*, *embedding_multiplier* and *logits_scaling* scale
    what a layer adds to the residual, the embedding as it enters, and (by
    division) the logits."""

    def __init__(self, vocab, dim, layer_types, num_dense_layers,
                 dense_hidden, expert_hidden, num_experts,
                 num_experts_per_tok, experts_held=None, first_expert=0,
                 expert_bias=None, norm_topk_prob=True,
                 routed_scaling_factor=1.0, heads=8, kv_heads=None,
                 head_dim=None, rope_theta=10000.0, conv_kernel=3,
                 eps=1e-5, weight_initializer="normal", shared_hidden=0,
                 tied_head=True, kv_lora_rank=None, qk_nope_head_dim=None,
                 qk_rope_head_dim=None, v_head_dim=None,
                 rope_interleave=True, scoring_func="sigmoid",
                 index_heads=None, index_head_dim=None, index_topk=None,
                 mrope_section=(), alignment_weight=1.0,
                 diffusion_block=None, rope_parameters=None,
                 sliding_window=None, attention_gate=False, linear=None,
                 norm_place=None, qk_norm=None, state_space=None,
                 attention_scale=None, residual_multiplier=1.0,
                 embedding_multiplier=1.0, logits_scaling=1.0, **kwargs):
        super().__init__(**kwargs)
        self._vocab, self._dim = vocab, dim
        self._embedding_multiplier = float(embedding_multiplier)
        self._logits_scaling = float(logits_scaling)
        layer_types = list(layer_types)
        if isinstance(heads, int):
            heads = [heads] * len(layer_types)
        if len(heads) != len(layer_types):
            raise ValueError("%d head counts for %d layers"
                             % (len(heads), len(layer_types)))
        rope_parameters = dict(rope_parameters or {})
        norm_place, qk_norm = dict(norm_place or {}), dict(qk_norm or {})
        self._two_copies = "block_diffusion_attention" in layer_types
        init = weight_initializer
        latent = kv_lora_rank and {
            "kv_lora_rank": kv_lora_rank,
            "qk_nope_head_dim": qk_nope_head_dim,
            "qk_rope_head_dim": qk_rope_head_dim, "v_head_dim": v_head_dim,
            "rope_interleave": rope_interleave}
        indexer = index_heads and {
            "index_heads": index_heads, "index_head_dim": index_head_dim,
            "topk": index_topk, "mrope_section": mrope_section}
        # the objective adds the MEAN of the sparse layers' alignment terms
        self._term_scale = float(alignment_weight) / max(
            1, list(layer_types).count("sparse_attention"))

        def dense():
            return GatedMLP(dim, dense_hidden, weight_initializer=init,
                            prefix="mlp_")

        def routed():
            return RoutedExperts(
                dim, expert_hidden, num_experts, num_experts_per_tok,
                experts_held, first_expert, expert_bias, norm_topk_prob,
                routed_scaling_factor, weight_initializer=init,
                scoring_func=scoring_func, prefix="moe_")

        def shared():
            return SharedExperts(dim, shared_hidden, weight_initializer=init,
                                 prefix="shared_")

        def shared_and_routed():
            return SharedAndRouted(routed, shared, prefix="ffn_")

        sparse = shared_and_routed if shared_hidden else routed
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(vocab, dim), init=init)
            self.layers = []
            for i, kind in enumerate(layer_types):
                grouped = None
                if kind in ("full_attention", "sliding_attention"):
                    grouped = {"gate": bool(attention_gate),
                               "rope": rope_parameters.get(kind)}
                    if kind == "sliding_attention":
                        grouped["window"] = sliding_window
                    if kind in qk_norm:
                        grouped["qk_norm"] = qk_norm[kind]
                    if attention_scale is not None:
                        grouped["scale"] = attention_scale
                layer = DecoderLayer(
                    dim, kind, dense if i < num_dense_layers else sparse,
                    heads[i], kv_heads or heads[i], head_dim, rope_theta,
                    conv_kernel, eps, init, latent, indexer,
                    diffusion_block, grouped, linear,
                    norm_place.get(kind, "input"), state_space,
                    residual_multiplier, prefix="l%d_" % i)
                setattr(self, "l%d" % i, layer)
                self.layers.append(layer)
            self.final_norm = nn.RMSNorm(dim, eps, prefix="final_norm_")
            self.head_weight = None if tied_head else self.params.get(
                "head_weight", shape=(vocab, dim), init=init)

    def hybrid_forward(self, F, x, positions=None, embed_weight=None,
                       head_weight=None):
        h = F.Embedding(x, embed_weight, input_dim=self._vocab,
                        output_dim=self._dim)
        if self._embedding_multiplier != 1.0:
            h = h * self._embedding_multiplier
        if self._two_copies and positions is None:
            positions = F.contrib.BlockDiffusionPositions(x)
        terms = []
        for layer in self.layers:
            h = layer(h) if positions is None else layer(h, positions)
            if isinstance(h, tuple):
                h, term = h
                terms.append(term)
        if self._two_copies:
            # the clean half is context only: no norm, no head, no logits
            h = F.split(h, num_outputs=2, axis=1)[1]
        logits = F.FullyConnected(
            self.final_norm(h),
            embed_weight if head_weight is None else head_weight,
            no_bias=True, flatten=False, num_hidden=self._vocab)
        if self._logits_scaling != 1.0:
            logits = logits / self._logits_scaling
        if not terms:
            return logits
        total = terms[0]
        for term in terms[1:]:
            total = total + term
        return logits, total * self._term_scale


class AlignedLoss(HybridBlock):
    """*loss* of a net's first output, with the net's second output (a
    `DecoderLM`'s alignment term) a part of the objective and not of the
    value: each row of the result is *loss*'s row plus ``term -
    stop_gradient(term)``, which is zero.  So what a step reports stays
    *loss*, and the term's gradient is scaled as every other gradient is, by
    however the rows are reduced and scaled afterwards (a trainer's mean, a
    plain ``backward()``'s sum, a loss scale)."""

    def __init__(self, loss, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.loss = loss

    def forward(self, outputs, label, *args):
        from ... import ndarray, symbol
        out, term = outputs
        F = symbol if isinstance(out, symbol.Symbol) else ndarray
        rows = self.loss(out, label, *args)
        return F.broadcast_add(rows, term - F.BlockGrad(term))


class BlockDiffusionLoss(HybridBlock):
    """The masked-diffusion objective of a net with
    block_diffusion_attention layers, one value a row: *pred* ``(batch, L,
    vocab)`` logits of the noised copy, *label* ``(batch, 2, L)`` float32,
    its planes the clean ids and the weights (``1 / t`` of the position's
    block where it was masked, else 0):

        ``(1 / L) * sum_i w_i * -log softmax(pred_i)[x_i]``

    summed and divided in float32 (`_contrib_BlockDiffusionLoss`, which
    also counts the positions that carry loss)."""

    def hybrid_forward(self, F, pred, label):
        return F.contrib.BlockDiffusionLoss(pred, label)


def get_decoder_lm(**kwargs):
    return DecoderLM(**kwargs)
