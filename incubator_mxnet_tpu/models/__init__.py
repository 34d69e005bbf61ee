"""Model zoo — the in-framework counterpart of the GluonCV/GluonNLP workloads
named in BASELINE.json (SURVEY §2.9): BERT pretraining, Transformer NMT,
image classification (LeNet/ResNet...), detection (SSD).

All models are HybridBlocks: eager for debugging, one ``hybridize()`` away
from a single XLA computation, and shardable over the parallel mesh with the
per-family ``*_sharding_rules()`` helpers.
"""
from ..gluon.block import HybridBlock
from . import transformer  # noqa: F401
from . import bert  # noqa: F401
from . import lenet  # noqa: F401
from .lenet import LeNet  # noqa: F401
from . import nmt  # noqa: F401
from .nmt import NMTModel, beam_search, beam_search_reference  # noqa: F401
from . import ssd  # noqa: F401
from .ssd import SSD, SSDTargetLoss  # noqa: F401
from . import rcnn  # noqa: F401
from .rcnn import FasterRCNN, RPN, FasterRCNNTargetLoss  # noqa: F401
from .transformer import (  # noqa: F401
    MultiHeadAttention, PositionwiseFFN, TransformerEncoderCell,
    StackedTransformerEncoder,
)
from .bert import (  # noqa: F401
    BERTModel, BERTEncoder, bert_sharding_rules, get_bert, bert_pretrain_loss,
)
from . import afmoe  # noqa: F401
from .afmoe import AfmoeModel, get_afmoe, afmoe_lm_loss  # noqa: F401
from . import deepseek_v3  # noqa: F401
from .deepseek_v3 import DeepseekV3Model, get_deepseek_v3  # noqa: F401
from . import lfm2_moe  # noqa: F401
from .lfm2_moe import Lfm2MoeModel, get_lfm2_moe  # noqa: F401
from . import granite_hybrid  # noqa: F401
from .granite_hybrid import GraniteHybridModel, get_granite_hybrid  # noqa: F401

#: Serving axis specs per model family — the ``input_axes``/``pad_values``
#: a ``serve.CompiledModel``/``ModelRegistry.load`` needs to bucket each
#: input correctly. Indexed by the *call signature* the family's serving
#: forward uses; ``valid_length`` pads with 0 so attention masks the fake
#: rows/positions (padding never leaks into real outputs).
SERVE_SPECS = {
    # BERTModel(ids, token_types, valid_length, masked_positions)
    "bert": {
        "input_axes": [{0: "batch", 1: "seq"}, {0: "batch", 1: "seq"},
                       {0: "batch"}, {0: "batch"}],
        "output_axes": [{0: "batch", 1: "seq"}, {0: "batch"},
                        {0: "batch"}, {0: "batch"}],
        "pad_values": [0, 0, 0, 0],
    },
    # BERTModel(ids, token_types, valid_length) with use_decoder=False,
    # use_classifier=False — encoder+pooler serving (embedding backends)
    "bert_encoder": {
        "input_axes": [{0: "batch", 1: "seq"}, {0: "batch", 1: "seq"},
                       {0: "batch"}],
        "output_axes": [{0: "batch", 1: "seq"}, {0: "batch"}],
        "pad_values": [0, 0, 0],
    },
    # LeNet(images) — fixed spatial dims, bucketed batch only
    "lenet": {
        "input_axes": [{0: "batch"}],
        "output_axes": [{0: "batch"}],
        "pad_values": [0],
    },
    # StackedTransformerEncoder(x, mask=None) served unmasked
    "transformer_encoder": {
        "input_axes": [{0: "batch", 1: "seq"}],
        "output_axes": [{0: "batch", 1: "seq"}],
        "pad_values": [0],
    },
    # NMTModel.encode(src_ids, src_len) — the beam-search entry's encoder
    "nmt_encoder": {
        "input_axes": [{0: "batch", 1: "seq"}, {0: "batch"}],
        "output_axes": [{0: "batch", 1: "seq"}],
        "pad_values": [0, 0],
    },
}


#: Families whose smoke model actually contains quantizable layers
#: (``nn.Dense``/``nn.Conv2D`` children the int8 graph pass can swap).
#: ``transformer_encoder`` is excluded: its stacked-parameter scan
#: encoder has no per-layer Dense children, so its "quantized" twin
#: would be a float copy. This is the quantized zoo every int8 consumer
#: iterates (``mxlint --hlo --quantized``, ``serve_bench --int8``,
#: the autotuner's ``quantize`` dimension).
QUANT_FAMILIES = ("bert", "bert_encoder", "lenet", "nmt_encoder")


def serve_spec(family: str) -> dict:
    """Copy of the named serving spec (see :data:`SERVE_SPECS`)."""
    if family not in SERVE_SPECS:
        raise KeyError(f"no serving spec for {family!r}; known: "
                       f"{sorted(SERVE_SPECS)}")
    spec = SERVE_SPECS[family]
    return {"input_axes": [dict(a) for a in spec["input_axes"]],
            "output_axes": [dict(a) for a in spec["output_axes"]],
            "pad_values": list(spec["pad_values"])}


class _NMTEncodeEntry(HybridBlock):
    """The ``nmt_encoder`` serving entry as a traceable block: the
    embed → masked-encoder half of ``NMTModel.encode``, built WITHOUT the
    decoder so the serving signature carries no dead decoder parameters
    (analysis.hlo MX703 would rightly flag them)."""

    def __init__(self, src_vocab=100, units=32, hidden_size=64,
                 num_layers=2, num_heads=2, max_length=32, **kw):
        super().__init__(**kw)
        from ..gluon import nn
        from .nmt import TransformerEncoder
        with self.name_scope():
            self.src_embed = nn.Embedding(src_vocab, units,
                                          prefix="src_embed_")
            self.encoder = TransformerEncoder(units, hidden_size,
                                              num_layers, num_heads, 0.1,
                                              max_length, prefix="enc_")

    def hybrid_forward(self, F, src, src_len):
        B, L = src.shape
        steps = F.arange(0, L, dtype="float32",
                         ctx=src_len.context).reshape((1, L))
        mask = F.broadcast_lesser(steps, src_len.reshape((B, 1)))
        return self.encoder(self.src_embed(src),
                            mask.reshape((B, 1, 1, L)))


def hlo_smoke(family: str, batch: int = None, seq: int = None) -> dict:
    """Small live instance of one serving family for compiled-graph
    analysis (``mxlint --hlo`` / CI ``hlo-lint``): returns ``{"block",
    "example_args", "table", "spec", "compiled"}`` sized so every bucket
    traces in milliseconds on CPU. ``compiled`` is THE un-warmed
    ``serve.CompiledModel`` every gate analyzes (building it never
    XLA-compiles — only :meth:`~...serve.CompiledModel.warmup` does), so
    the CLI target and the tests provably check the same object shape.

    ``batch``/``seq`` override the bucket geometry with a SINGLE bucket
    of that size (example args sized to fill it) — the knob
    ``benchmark.autotune`` turns to price batch/bucket-geometry
    candidates through the exact entry the gates analyze. Defaults keep
    the historical two-bucket ladders, so every existing caller traces
    byte-identical graphs."""
    import numpy as onp

    from .. import nd, serve

    spec = serve_spec(family)
    B = int(batch) if batch else 2
    batch_lad = (int(batch), int(batch)) if batch else (1, 4)
    L = int(seq) if seq else 16
    seq_lad = (int(seq), int(seq)) if seq else (8, 16)
    if family in ("bert", "bert_encoder"):
        vocab, P = 1000, 4
        if L > 32:
            raise ValueError(f"hlo_smoke({family!r}) probe caps seq at 32 "
                             f"(position table), got {L}")
        net = get_bert("bert_2_128_2", vocab_size=vocab, max_length=32,
                       dropout=0.1, use_decoder=(family == "bert"),
                       use_classifier=(family == "bert"))
        net.initialize()
        net.hybridize()
        ids = nd.array(onp.ones((B, L), "int32"))
        tt = nd.array(onp.zeros((B, L), "int32"))
        vl = nd.array(onp.full((B,), L, "float32"))
        if family == "bert":
            pos = nd.array(onp.zeros((B, P), "int32"))
            args = (ids, tt, vl, pos)
        else:
            args = (ids, tt, vl)
        table = serve.BucketTable({"batch": batch_lad, "seq": seq_lad})
    elif family == "lenet":
        net = LeNet()
        net.initialize()
        net.hybridize()
        args = (nd.array(onp.zeros((B, 1, 28, 28), "float32")),)
        table = serve.BucketTable({"batch": batch_lad})
    elif family == "transformer_encoder":
        net = StackedTransformerEncoder(num_layers=2, units=32,
                                        hidden_size=64, num_heads=2)
        net.initialize()
        net.hybridize()
        args = (nd.array(onp.zeros((B, L, 32), "float32")),)
        table = serve.BucketTable({"batch": batch_lad, "seq": seq_lad})
    elif family == "nmt_encoder":
        if L > 32:
            raise ValueError(f"hlo_smoke({family!r}) probe caps seq at 32 "
                             f"(position table), got {L}")
        net = _NMTEncodeEntry()
        net.initialize()
        net.hybridize()
        args = (nd.array(onp.ones((B, L), "int32")),
                nd.array(onp.full((B,), L, "float32")))
        table = serve.BucketTable({"batch": batch_lad, "seq": seq_lad})
    else:
        raise KeyError(f"no hlo smoke model for {family!r}; known: "
                       f"{sorted(SERVE_SPECS)}")
    net(*args)
    compiled = serve.CompiledModel(net, table, spec["input_axes"],
                                   example_args=args,
                                   output_axes=spec["output_axes"],
                                   pad_values=spec["pad_values"],
                                   autotune_key=family)
    return {"block": net, "example_args": args, "table": table,
            "spec": spec, "compiled": compiled}


def calib_args(family: str, batch: int = None, seq: int = None,
               seed: int = 0) -> tuple:
    """Seeded non-degenerate inputs for ``family``'s serving signature —
    the calibration batch :func:`quantized_smoke` observes. The zoo's
    ``hlo_smoke`` example args are mostly zeros (fine for tracing,
    useless for calibration: every range collapses), so calibration data
    is drawn separately: float tensors ~N(0,1), ids uniform over the
    probe vocab, valid lengths full."""
    import numpy as onp

    from .. import nd

    sm_args = hlo_smoke(family, batch=batch, seq=seq)["example_args"]
    rs = onp.random.RandomState(seed)
    out = []
    for a in sm_args:
        arr = onp.asarray(a.asnumpy())
        if arr.dtype.kind == "f":
            if arr.ndim == 1:          # valid_length-style: keep full
                out.append(nd.array(arr))
            else:
                out.append(nd.array(
                    rs.randn(*arr.shape).astype(arr.dtype)))
        else:                          # ids: uniform over the probe vocab
            hi = max(int(arr.max()) + 1, 32)
            out.append(nd.array(
                rs.randint(0, hi, arr.shape).astype(arr.dtype)))
    return tuple(out)


def quantized_smoke(family: str, batch: int = None, seq: int = None,
                    percentile: float = None) -> dict:
    """The quantized twin of :func:`hlo_smoke`: calibrate the family's
    smoke model on a seeded batch (:func:`calib_args` →
    ``quantization.observe_net``) and lower the Observer through
    ``quantization.quantize_model`` into a quantized
    ``serve.CompiledModel`` sharing the float model's bucket table,
    axes, pad values, and ``autotune_key``.

    This is THE quantized-zoo entry every int8 consumer analyzes —
    ``mxlint --hlo --quantized``, the autotune ``quantize`` dimension,
    ``serve_bench --int8``, ``benchmark/int8_probe.py``, and the
    ``<family>_int8`` proxy records — so the graphs CI lints, the graphs
    the roofline prices, and the graphs the bench runs are provably the
    same. Deterministic: same family/geometry → byte-identical int8
    weights and ranges.

    Returns ``{"block", "example_args", "table", "spec", "compiled",
    "observer", "f32"}`` — ``compiled`` is the quantized model,
    ``f32`` the full float ``hlo_smoke`` dict it was derived from.
    """
    from .. import quantization as _quant

    sm = hlo_smoke(family, batch=batch, seq=seq)
    cargs = calib_args(family, batch=batch, seq=seq)
    observer = _quant.observe_net(sm["block"], [cargs])
    qcm = _quant.quantize_model(sm["compiled"], observer,
                                percentile=percentile)
    return {"block": qcm._block, "example_args": sm["example_args"],
            "table": sm["table"], "spec": sm["spec"], "compiled": qcm,
            "observer": observer, "f32": sm}
