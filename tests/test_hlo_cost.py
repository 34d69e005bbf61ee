"""The device-blind cost model: analysis.hlo.cost, the MX707 informational
pass and mxlint --cost. Counts from a CPU trace, never device metrics."""
import json

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx  # noqa: F401  (repo on path)
from incubator_mxnet_tpu import models
from incubator_mxnet_tpu.analysis import hlo


# ---------------------------------------------------------------------------
# the cost model
# ---------------------------------------------------------------------------
class TestCostModel:
    def test_matmul_flops_exact(self):
        w = onp.ones((8, 16), "float32")
        rep = hlo.cost(lambda x: x @ w,
                       sample_args=(onp.zeros((4, 8), "float32"),))
        r = rep.rows[0]
        assert r.flops == 2 * 4 * 16 * 8          # 2*M*N*K
        assert r.matmul_flops == r.flops
        assert r.input_bytes == 4 * 8 * 4
        assert r.output_bytes == 4 * 16 * 4

    def test_transcendentals_and_fusion(self):
        import jax.numpy as jnp
        rep = hlo.cost(lambda x: jnp.tanh(x * 2.0) + 1.0,
                       sample_args=(onp.zeros((8,), "float32"),))
        r = rep.rows[0]
        assert r.transcendentals == 8
        # mul -> tanh -> add is one def-use-connected fusible group
        assert r.fusible_eqns == 3
        assert r.fusion_groups == 1 and r.fusion_candidates == 1
        assert r.unknown_eqns == 0

    def test_cost_is_deterministic(self):
        smoke = models.hlo_smoke("lenet")
        a = hlo.cost(smoke["compiled"], max_graphs=8).to_dict()
        b = hlo.cost(smoke["compiled"], max_graphs=8).to_dict()
        assert a == b                              # the CI-gate property

    def test_cost_over_serving_family(self):
        smoke = models.hlo_smoke("lenet")
        rep = hlo.cost(smoke["compiled"], max_graphs=8)
        assert rep.rows and all(r.flops > 0 for r in rep.rows)
        head = rep.head
        # param bytes are exactly the model's parameter footprint
        expected = sum(
            int(onp.prod(p.shape)) * onp.dtype(str(p.dtype)).itemsize
            for p in smoke["compiled"]._pvals)
        assert head.param_bytes == expected
        assert rep.model_flops_per_step() == max(r.flops for r in rep.rows)
        assert rep.bytes_per_step() == (head.param_bytes + head.input_bytes
                                        + head.output_bytes)
        assert "LeNet" in rep.text_table()

    def test_trainer_step_graph_is_train_kind(self):
        import jax
        from incubator_mxnet_tpu import gluon, parallel
        net = gluon.nn.HybridSequential(prefix="costtrain_")
        with net.name_scope():
            net.add(gluon.nn.Dense(4, in_units=8))
        net.initialize()
        l2 = gluon.loss.L2Loss()
        mesh = parallel.make_mesh(devices=jax.devices()[:1])
        trainer = parallel.ShardedTrainer(
            net, lambda out, label: l2(out, label), "sgd",
            {"learning_rate": 0.01}, mesh=mesh, n_labels=1)
        x = onp.zeros((2, 8), "float32")
        y = onp.zeros((2, 4), "float32")
        trainer.step(x, y).asnumpy()
        rep = hlo.cost(trainer, sample_args=(x, y))
        r = rep.rows[0]
        assert r.kind == "train"
        # fwd+bwd+optimizer must cost more than the inference forward
        infer = hlo.cost(lambda v: v @ onp.zeros((8, 4), "float32"),
                         sample_args=(x,)).rows[0]
        assert r.flops > infer.flops
        assert r.param_bytes > 0


# ---------------------------------------------------------------------------
# MX707 informational pass (opt-in)
# ---------------------------------------------------------------------------
class TestMX707:
    def test_opt_in_emits_info_rows(self):
        smoke = models.hlo_smoke("lenet")
        rep = hlo.verify(smoke["compiled"], cost=True)
        infos = rep.infos
        assert infos and all(d.code == "MX707" for d in infos)
        assert all(d.severity == "info" for d in infos)
        assert rep.ok                      # info never gates
        assert "FLOPs" in infos[0].message

    def test_default_verify_stays_signal_only(self):
        smoke = models.hlo_smoke("lenet")
        rep = hlo.verify(smoke["compiled"])
        assert not rep.infos
        assert "MX707" not in rep.codes()


# ---------------------------------------------------------------------------
# mxlint --cost
# ---------------------------------------------------------------------------
@pytest.mark.lint
class TestMxlintCost:
    def test_cost_flag_json(self, capsys):
        from tools.mxlint import main
        rc = main(["--hlo", "lenet", "--cost", "--format=json"])
        assert rc == 0
        out = capsys.readouterr().out
        rows = [json.loads(l) for l in out.strip().splitlines()]
        cost_rows = [r for r in rows if r.get("kind") == "cost"]
        mx707 = [r for r in rows if r.get("code") == "MX707"]
        assert cost_rows and mx707
        assert cost_rows[0]["target"] == "lenet"
        assert cost_rows[0]["flops"] > 0
        assert cost_rows[0]["graph_kind"] == "infer"

    def test_cost_flag_text_table(self, capsys):
        from tools.mxlint import main
        rc = main(["--hlo", "lenet", "--cost"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "== cost: lenet ==" in out
        assert "model_flops_per_step" in out

    def test_cost_without_hlo_is_bad_invocation(self, capsys):
        from tools.mxlint import main
        assert main(["--cost"]) == 2
