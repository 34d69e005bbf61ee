"""Model-zoo hybridize consistency (reference: hybridize-consistency checks in
test_gluon.py). One model family per file: under ``--dist loadfile`` a file
is what one worker runs, and NMT, SSD and the zoo together were 346 s of
one."""
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx


@pytest.mark.parametrize("name", ["vgg11", "densenet121", "mobilenetv2_1.0",
                                  "squeezenet1.1"])
def test_zoo_hybridize_matches_eager(name):
    """CachedOp correctness across the zoo families: the jit-compiled
    forward must reproduce the eager forward bit-for-bit at fp32 tolerance
    (reference mechanism: hybridize-consistency checks in test_gluon.py)."""
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    net = vision.get_model(name, classes=5)
    net.initialize()
    x = mx.nd.array(onp.random.RandomState(0)
                    .rand(1, 3, 32, 32).astype("float32"))
    with mx.autograd.predict_mode():
        eager = net(x).asnumpy()
        net.hybridize()
        compiled = net(x).asnumpy()
    onp.testing.assert_allclose(compiled, eager, rtol=2e-5, atol=2e-6)
