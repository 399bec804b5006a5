"""The frame of the expert-layer decoders (``zaya``, ``qwen3_next``,
``kanana2``, ``keye_vl2``, ``smallthinker``, ``sdar_moe``,
``kimi_linear``): everything
such a model is apart from its layers.  A family's file says its mixer,
its expert sublayer's attributes and its layer schedule; token ids enter
and logits leave here:

* ``experts_held``, the chip's share of a layer's experts (how many,
  from expert 0, or ``[first, count]``), parsed and checked;
* the float32 token embedding (the lookup and the gradient's
  scatter-add stay exact) and its cast to the trunk's dtype;
* the family's RMSNorm, under the names ``<name>`` / ``<name>_gamma``;
* the closing norm, the head (its own matrix, or the embedding's in the
  trunk's dtype), float32 logits flattened to (tokens, vocab) and the
  ``SoftmaxOutput`` normalised by the batch: output 0, what ``ce``
  reads; a family whose objective is not the plain mean over all rows
  (the seventh, ``sdar_moe``: a masked, weighted denoising loss) hands
  ``close`` a hook that stands between ``lm_head`` and the cast, so
  that the head's chain stays the deferred one (``loss_head.py``);
* the experts' token counts of every expert sublayer, stacked to
  (expert layers, num_experts) int32 behind ``BlockGrad`` under
  ``telemetry.moe.COUNTS_NODE``.

Every node and argument is named here as the families named it: weights
are matched to a reference by name, checkpoints name them, and the
compile cache is keyed by the program
(``tests/test_models.py::test_a_decoder_family_builds_the_graph_it_built``).
"""
from .. import initializer as _init
from .. import symbol as sym
from ..telemetry.moe import COUNTS_NODE

F32 = {"dtype": "float32"}      # a router, a scorer, a decay: whatever dtype


def weight(name, init=_init.Normal(0.02), **kw):
    return sym.Variable(name, init=init, **kw)


class Decoder:
    """One model's frame: ``embed()``, the family's layers over the
    stream with ``norm``, ``first`` and ``held``, then ``close()``.
    ``norm_init`` and ``norm_attrs`` are the family's RMSNorm (its
    gain's initial value; ``eps``, ``zero_centered``,
    ``force_mirroring``)."""

    def __init__(self, num_classes, d_model, num_experts, experts_held,
                 dtype, norm_init=_init.One(), **norm_attrs):
        self.vocab, self.d, E = int(num_classes), int(d_model), int(num_experts)
        if experts_held is None:
            first, held = 0, E
        elif isinstance(experts_held, int):
            first, held = 0, int(experts_held)
        else:
            first, held = (int(v) for v in experts_held)
        if not (0 <= first and 0 < held and first + held <= E):
            raise ValueError("experts_held=%r is no part of %d experts"
                             % (experts_held, E))
        self.first, self.held = first, held
        self.dtype, self.low = dtype, dtype in ("float16", "bfloat16")
        self.norm_init, self.norm_attrs = norm_init, norm_attrs

    def norm(self, x, name):
        return sym.RMSNorm(x, gamma=weight(name + "_gamma", self.norm_init),
                           name=name, **self.norm_attrs)

    def embed(self, init=_init.Normal(1.0), ids=None, cast=True):
        """(B, S) token ids (``data``, or ``ids`` where the family cuts
        them out of ``data`` itself) -> the stream (B, S, d_model) in the
        trunk's dtype; with ``cast=False`` in the table's float32 (a
        family that keeps its residual stream float32: ``sdar_moe``)."""
        self.table = weight("tok_embed_weight", init,
                            shape=(self.vocab, self.d), **F32)
        x = sym.Embedding(sym.Variable("data") if ids is None else ids,
                          weight=self.table,
                          input_dim=self.vocab, output_dim=self.d,
                          name="tok_embed")
        return sym.Cast(data=x, dtype=self.dtype, name="cast_embed") \
            if self.low and cast else x

    def close(self, x, counts, tied=False, rows=None):
        """The stream after the last layer and the expert sublayers'
        counts -> ``[softmax, counts]``, which the family groups (with
        what it appends).  ``tied``: the head is the embedding, read in
        the trunk's dtype.  ``rows``: the family's hook on the logits as
        ``lm_head`` wrote them (trunk's dtype, (B, S, vocab)), before
        the cast: what it returns is the loss head's stem."""
        if not tied:
            head = weight("lm_head_weight")
        elif self.low:
            head = sym.Cast(data=self.table, dtype=self.dtype,
                            name="cast_head")
        else:
            head = self.table
        logits = sym.FullyConnected(data=self.norm(x, "final_norm"),
                                    weight=head, no_bias=True,
                                    num_hidden=self.vocab, flatten=False,
                                    name="lm_head")
        if rows is not None:
            logits = rows(logits)
        if self.low:
            logits = sym.Cast(data=logits, dtype="float32", name="cast_out")
        flat = sym.Reshape(data=logits, shape=(-1, self.vocab),
                           name="logits_2d")
        out = sym.SoftmaxOutput(data=flat, name="softmax",
                                normalization="batch")
        tokens = sym.BlockGrad(
            sym.stack(*counts, axis=0, name="moe_tokens_all"),
            name=COUNTS_NODE)
        return [out, tokens]
