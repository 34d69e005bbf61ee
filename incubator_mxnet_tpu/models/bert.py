"""BERT — the flagship (north-star) model family.

Reference parity: GluonNLP ``scripts/bert/`` + ``gluonnlp/model/bert.py``
(BERTEncoder, BERTModel with use_pooler/use_decoder/use_classifier), running
on the contrib interleaved-MHA ops (SURVEY §2.9: the BASELINE.json north-star
workload). Same forward contract as GluonNLP:

    seq, pooled, nsp, mlm = model(ids, token_types, valid_length, positions)

TPU-native design: the whole pretraining step — embeddings, N encoder layers
on flash attention, both heads, loss, grads, AdamW/LAMB update — compiles to
ONE XLA executable via ``parallel.ShardedTrainer`` with
:func:`bert_sharding_rules` (Megatron-style TP over the ``tp`` mesh axis,
batch over ``dp``, sequence over ``sp``); bf16 activations via ``dtype``.
"""
from __future__ import annotations

from typing import Optional

from ..gluon.block import HybridBlock
from ..gluon import nn, loss as loss_mod
from .transformer import TransformerEncoderCell

__all__ = ["BERTEncoder", "BERTModel", "bert_sharding_rules", "get_bert",
           "bert_pretrain_loss", "BERT_CONFIGS"]

#: GluonNLP model-name convention: bert_<layers>_<units>_<heads>
BERT_CONFIGS = {
    "bert_2_128_2": dict(num_layers=2, units=128, hidden_size=512,
                         num_heads=2),          # tiny (tests)
    "bert_12_768_12": dict(num_layers=12, units=768, hidden_size=3072,
                           num_heads=12),       # base
    "bert_24_1024_16": dict(num_layers=24, units=1024, hidden_size=4096,
                            num_heads=16),      # large
}


class BERTEncoder(HybridBlock):
    """Stack of post-LN transformer encoder cells.

    ``remat=True`` wraps each cell in ``jax.checkpoint`` when the stack is
    compiled (hybridize / ShardedTrainer): activations inside a layer are
    rematerialized in backward instead of living in HBM across the whole
    stack — O(L·C·1) live activations instead of O(L·C·layers), the lever
    that lets BERT-large batches fill the chip (SURVEY §7 "jax.checkpoint /
    rematerialisation"). One thing is held and not rebuilt: what the flash
    attention kernel returned, its output and log-sum-exp
    (``ops.attention.checkpoint_layer``) — 17 MB a layer for BERT-large at
    B=16, 68 MB for a decoder at 8k tokens, against a second run of the
    step's most expensive kernel. On the XLA attention path nothing is held.
    No effect on eager execution.
    """

    def __init__(self, num_layers: int, units: int, hidden_size: int,
                 num_heads: int, dropout: float = 0.1, dtype="float32",
                 weight_initializer=None, remat: bool = False, **kwargs):
        super().__init__(**kwargs)
        self._num_layers = num_layers
        self._remat = remat
        self._dropout = dropout
        with self.name_scope():
            self.layers = []
            for i in range(num_layers):
                cell = TransformerEncoderCell(
                    units, hidden_size, num_heads, dropout=dropout,
                    dtype=dtype, prefix=f"layer{i}_",
                    weight_initializer=weight_initializer)
                self.register_child(cell, f"layer{i}")
                self.layers.append(cell)

    def hybrid_forward(self, F, x, mask=None):
        from ..gluon.block import _is_tracing
        import jax
        # checkpoint only under a REAL jit trace: the ShardedTrainer warmup
        # runs eagerly with the tracing flag set (to finish deferred init),
        # and an eager jax.checkpoint would trace deferred param init into
        # its region — the init value would then be a region-local tracer
        # stored on the Parameter (UnexpectedTracerError on reuse).
        if self._remat and _is_tracing() \
                and isinstance(x._data, jax.core.Tracer):
            from .. import random as random_mod
            from ..ndarray import NDArray
            from ..ops.attention import checkpoint_layer
            need_rng = self._dropout > 0
            for cell in self.layers:
                # jax.checkpoint over the cell body; params/mask are
                # closed-over tracers (new-style remat closure-converts
                # them, cotangents flow). RNG must NOT be stateful across
                # the checkpoint boundary: a next_key() split inside the
                # region would store a region-local tracer in the ambient
                # trace_rng (UnexpectedTracerError). Instead draw one key
                # per layer at the outer trace level and thread it in as a
                # checkpoint ARGUMENT — backward's recompute then replays
                # the exact same dropout masks by construction.
                if need_rng:
                    layer_key = random_mod.next_key()

                    def body(xv, kv, cell=cell, mask=mask, ctx=x.context):
                        with random_mod.trace_rng(kv):
                            return cell(NDArray(xv, ctx=ctx), mask)._data

                    x = NDArray(checkpoint_layer(body)(x._data, layer_key),
                                ctx=x.context)
                else:
                    def body(xv, cell=cell, mask=mask, ctx=x.context):
                        return cell(NDArray(xv, ctx=ctx), mask)._data

                    x = NDArray(checkpoint_layer(body)(x._data), ctx=x.context)
            return x
        for cell in self.layers:
            x = cell(x, mask)
        return x


class BERTModel(HybridBlock):
    """BERT with optional pooler (NSP input), MLM decoder and NSP classifier.

    ``forward(inputs, token_types, valid_length=None, masked_positions=None)``
    returns, depending on the ``use_*`` flags (GluonNLP contract):
    ``seq_out`` | ``(seq_out, pooled)`` | ``(seq_out, pooled, nsp)`` |
    ``(seq_out, pooled, nsp, mlm)``.
    """

    def __init__(self, vocab_size: int, units: int = 768,
                 hidden_size: int = 3072, num_layers: int = 12,
                 num_heads: int = 12, max_length: int = 512,
                 token_type_vocab_size: int = 2, dropout: float = 0.1,
                 use_pooler: bool = True, use_decoder: bool = True,
                 use_classifier: bool = True, dtype="float32",
                 embed_initializer=None, remat: bool = False, **kwargs):
        super().__init__(**kwargs)
        self._vocab_size = vocab_size
        self._units = units
        self._max_length = max_length
        if use_classifier and not use_pooler:
            raise ValueError("use_classifier=True requires use_pooler=True "
                             "(the NSP head reads the pooled [CLS] vector)")
        self.use_pooler = use_pooler
        self.use_decoder = use_decoder
        self.use_classifier = use_classifier
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype,
                                           prefix="word_embed_",
                                           weight_initializer=embed_initializer)
            self.token_type_embed = nn.Embedding(
                token_type_vocab_size, units, dtype=dtype,
                prefix="token_type_embed_", weight_initializer=embed_initializer)
            self.position_weight = self.params.get(
                "position_weight", shape=(max_length, units), dtype=dtype,
                init=embed_initializer)
            self.embed_ln = nn.LayerNorm(epsilon=1e-12, in_channels=units,
                                         prefix="embed_ln_")
            self.embed_dropout = nn.Dropout(dropout) if dropout else None
            self.encoder = BERTEncoder(num_layers, units, hidden_size,
                                       num_heads, dropout=dropout, dtype=dtype,
                                       prefix="encoder_", remat=remat)
            if use_pooler:
                self.pooler = nn.Dense(units, flatten=False, in_units=units,
                                       activation="tanh", prefix="pooler_",
                                       dtype=dtype)
            if use_classifier:
                self.classifier = nn.Dense(2, flatten=False, in_units=units,
                                           prefix="nsp_", dtype=dtype)
            if use_decoder:
                self.decoder_transform = nn.Dense(
                    units, flatten=False, in_units=units, activation="gelu",
                    prefix="decoder_transform_", dtype=dtype)
                self.decoder_ln = nn.LayerNorm(epsilon=1e-12, in_channels=units,
                                               prefix="decoder_ln_")
                # Output projection is TIED to the word embedding (reference:
                # GluonNLP BERTModel._decode shares word_embed params).
                self.decoder_tied_weight = self.word_embed.weight
                self.decoder_bias = self.params.get(
                    "decoder_bias", shape=(vocab_size,), init="zeros",
                    dtype=dtype)

    # -- helpers -----------------------------------------------------------
    def _attn_mask(self, F, valid_length, B, L):
        if valid_length is None:
            return None
        # made where the input lives: run eagerly on another context than
        # the current one, a default-context arange would meet
        # valid_length across devices
        steps = F.arange(0, L, dtype="float32",
                         ctx=valid_length.context).reshape((1, L))
        mask = F.broadcast_lesser(steps, valid_length.reshape((B, 1)))
        return mask.reshape((B, 1, 1, L))

    def hybrid_forward(self, F, inputs, token_types=None, valid_length=None,
                       masked_positions=None, position_weight=None,
                       decoder_tied_weight=None, decoder_bias=None):
        B, L = inputs.shape[0], inputs.shape[1]
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        pos = F.slice_axis(position_weight, axis=0, begin=0, end=L)
        x = x + pos.reshape((1, L, self._units))
        x = self.embed_ln(x)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        mask = self._attn_mask(F, valid_length, B, L)
        seq = self.encoder(x, mask)
        outputs = [seq]
        pooled = None
        if self.use_pooler:
            cls = F.slice_axis(seq, axis=1, begin=0, end=1).reshape(
                (B, self._units))
            pooled = self.pooler(cls)
            outputs.append(pooled)
        if self.use_classifier:
            outputs.append(self.classifier(pooled))
        if self.use_decoder and masked_positions is not None:
            P = masked_positions.shape[1]
            flat = seq.reshape((B * L, self._units))
            offsets = F.arange(0, B, dtype="int32",
                               ctx=seq.context).reshape((B, 1)) * L
            idx = (masked_positions.astype("int32") + offsets).reshape((B * P,))
            h = F.take(flat, idx, axis=0).reshape((B, P, self._units))
            h = self.decoder_ln(self.decoder_transform(h))
            mlm = F.FullyConnected(h, decoder_tied_weight, decoder_bias,
                                   num_hidden=self._vocab_size, flatten=False)
            outputs.append(mlm)
        return tuple(outputs) if len(outputs) > 1 else outputs[0]


def get_bert(name_or_cfg="bert_12_768_12", vocab_size: int = 30522,
             max_length: int = 512, dropout: float = 0.1, dtype="float32",
             **overrides) -> BERTModel:
    """Model-zoo constructor (reference: gluonnlp.model.get_model('bert_...'))."""
    cfg = dict(BERT_CONFIGS[name_or_cfg]) if isinstance(name_or_cfg, str) \
        else dict(name_or_cfg)
    cfg.update(overrides)
    return BERTModel(vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, dtype=dtype, **cfg)


def bert_sharding_rules(extra=()):
    """Megatron-style TP rules for :class:`parallel.ShardedTrainer`.

    Dense weights are (out, in): qkv/ffn1 split the output dim over ``tp``
    (column-parallel), proj/ffn2 split the input dim (row-parallel) so XLA
    inserts exactly one reduce per block; embeddings shard the vocab dim.
    """
    from ..parallel.sharding import P, ShardingRules
    return ShardingRules(list(extra) + [
        (r".*qkv_weight", P("tp", None)),
        (r".*qkv_bias", P("tp")),
        (r".*(proj|ffn2)_weight", P(None, "tp")),
        (r".*ffn1_weight", P("tp", None)),
        (r".*ffn1_bias", P("tp")),
        (r".*word_embed_weight", P("tp", None)),
        (r".*decoder_bias", P("tp")),
    ])


def bert_pretrain_loss(outputs, mlm_labels, mlm_weights, nsp_labels):
    """Combined MLM + NSP loss (reference: scripts/bert/pretraining_utils.py).

    ``outputs`` = BERTModel 4-tuple; ``mlm_labels/mlm_weights`` (B, P) with
    weight 0 on padding positions; ``nsp_labels`` (B,).
    """
    _, _, nsp_scores, mlm_scores = outputs
    ce = loss_mod.SoftmaxCrossEntropyLoss()
    mlm = ce(mlm_scores, mlm_labels, mlm_weights.expand_dims(-1))
    denom = mlm_weights.mean() + 1e-8
    nsp = ce(nsp_scores, nsp_labels)
    return mlm.mean() / denom + nsp.mean()
