"""NMT Transformer model tests (reference: GluonNLP machine_translation suite
— BASELINE.json config 4). One model family per file: under ``--dist
loadfile`` a file is what one worker runs, and NMT, SSD and the zoo together
were 346 s of one."""
import numpy as onp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, models


def _nmt():
    net = models.NMTModel(src_vocab=40, tgt_vocab=45, units=32, hidden_size=64,
                          num_layers=2, num_heads=2, dropout=0.0,
                          max_length=32)
    net.initialize()
    return net


def test_nmt_forward_and_tied_embedding():
    net = _nmt()
    rng = onp.random.RandomState(0)
    src = mx.nd.array(rng.randint(3, 40, (2, 9)), dtype="int32")
    tgt = mx.nd.array(rng.randint(3, 45, (2, 7)), dtype="int32")
    with mx.autograd.predict_mode():
        out = net(src, tgt)
    assert out.shape == (2, 7, 45)
    assert net.proj_weight is net.tgt_embed.weight


def test_nmt_training_reduces_loss():
    net = _nmt()
    rng = onp.random.RandomState(1)
    src = mx.nd.array(rng.randint(3, 40, (4, 8)), dtype="int32")
    tgt = mx.nd.array(rng.randint(3, 45, (4, 6)), dtype="int32")
    lab = mx.nd.array(rng.randint(3, 45, (4, 6)), dtype="float32")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "adam", {"learning_rate": 3e-3})
    losses = []
    for _ in range(8):
        with mx.autograd.record():
            l = loss_fn(net(src, tgt), lab).mean()
        l.backward()
        tr.step(4)
        losses.append(float(l.asnumpy()))
    assert losses[-1] < losses[0]


def test_beam_search_shapes_and_order():
    net = _nmt()
    rng = onp.random.RandomState(2)
    src = rng.randint(3, 40, (3, 7)).astype("int32")
    seqs, scores = models.beam_search(net, src, beam_size=4, max_length=5)
    assert seqs.shape == (3, 4, 5)
    assert scores.shape == (3, 4)
    s = onp.asarray(scores)
    assert (onp.diff(s, axis=1) <= 1e-6).all()  # sorted best-first
