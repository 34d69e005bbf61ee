"""SSD model tests (reference: GluonCV SSD suite — BASELINE.json config 5).
One model family per file: under ``--dist loadfile`` a file is what one
worker runs, and NMT, SSD and the zoo together were 346 s of one."""
import numpy as onp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, models


def test_ssd_shapes_consistent():
    net = models.SSD(num_classes=2)
    net.initialize()
    x = mx.nd.array(onp.random.rand(1, 3, 64, 64).astype("float32"))
    with mx.autograd.predict_mode():
        cls_preds, box_preds, anchor = net(x)
    N = anchor.shape[1]
    assert cls_preds.shape == (1, N, 3)
    assert box_preds.shape == (1, N * 4)
    det = net.detect(x)
    assert det.shape == (1, N, 6)


def test_ssd_loss_trains():
    net = models.SSD(num_classes=2)
    net.initialize()
    loss_fn = models.SSDTargetLoss()
    rng = onp.random.RandomState(3)
    x = mx.nd.array(rng.rand(2, 3, 64, 64).astype("float32"))
    label = mx.nd.array(onp.array([[[0.0, 0.2, 0.2, 0.6, 0.6]],
                                   [[1.0, 0.4, 0.4, 0.8, 0.8]]], "float32"))
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 5e-3})
    first = None
    for _ in range(5):
        with mx.autograd.record():
            cp, bp, an = net(x)
            l = loss_fn(cp, bp, an, label)
        l.backward()
        tr.step(2)
        v = float(l.asnumpy())
        first = v if first is None else first
    assert v < first
