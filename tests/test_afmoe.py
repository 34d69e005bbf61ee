"""AFMoE (Trinity-Mini's block) against its plain reference, at a tiny size:
2 K/V heads x 2, 8 experts top 2 with 4 held, a window shorter than L, one
dense layer and one period (sliding x3, full). Also the kernels it brought:
grouped K/V heads in the flash kernels (interpret mode) and the grouped
matmul over ragged groups."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import models, parallel
from incubator_mxnet_tpu.ops.attention import dot_product_attention
from incubator_mxnet_tpu.ops.pallas import flash_attention as fa
from incubator_mxnet_tpu.ops.pallas import moe_gmm, moe_rows
from incubator_mxnet_tpu.parallel import moe_dropless

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import moe_gmm_probe as probe  # noqa: E402
from chipbench.reference import afmoe as reference  # noqa: E402

CFG = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           intermediate_size=128, moe_intermediate_size=32, num_experts=8,
           num_experts_per_tok=2, num_shared_experts=1, num_dense_layers=1,
           layer_types=["sliding_attention"] * 4 + ["full_attention"],
           sliding_window=16, rope_theta=10000, rms_norm_eps=1e-5, route_norm=True,
           route_scale=2.826, mup_enabled=True, vocab_size=96,
           experts_held=4, expert_first=4, moe_tile_rows=8)
B, L = 2, 32


def _batch(seed=0):
    rng = onp.random.default_rng(seed)
    seq = rng.integers(0, CFG["vocab_size"], (B, L + 1)).astype("int32")
    return (seq[:, :L], onp.tile(onp.arange(L, dtype="int32"), (B, 1)),
            onp.array([L, L * 3 // 4], "float32"), seq[:, 1:])


def _net(seed=3, **kwargs):
    mx.random.seed(seed)
    net = models.get_afmoe(CFG, **kwargs)
    net.initialize(mx.init.Normal(0.05))
    return net


def _params(net):
    return {k[len(net.prefix):]: p.data()._data for k, p in net.collect_params().items()}


@pytest.fixture(scope="module")
def system_and_reference():
    """One forward of the program and of the reference on the same seeded
    weights, and the parameter gradients of both: the program's through the
    trainer's compiled step (SGD at rate 1: gradient = old - new weight),
    with every layer recomputed in the backward pass (``remat``)."""
    net = _net(remat=True)
    ids, pos, vl, lab = _batch()
    args = [mx.nd.array(a, dtype=a.dtype) for a in (ids, pos, vl)]
    logits, valid = net(*args)
    loss = models.afmoe_lm_loss((logits, valid), mx.nd.array(lab, dtype="int32"))
    params = _params(net)

    def ref_loss(p):
        out = reference.forward(p, CFG, ids, pos, vl)
        return reference.lm_loss(out["logits"], out["valid"], lab), out

    (r_loss, r_out), r_grads = jax.jit(jax.value_and_grad(ref_loss, has_aux=True))(params)
    trainer = parallel.ShardedTrainer(
        net, models.afmoe_lm_loss, "sgd", dict(learning_rate=1.0),
        mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1)
    trainer.step(ids, pos, vl, lab)
    trainer.sync_to_block()
    grads = {k: params[k] - v for k, v in _params(net).items()}
    return dict(logits=onp.asarray(logits.asnumpy()), loss=float(loss.asnumpy()),
                grads=grads, trainer=trainer, net=net, args=args, r_out=r_out, r_loss=float(r_loss),
                r_grads=r_grads, valid=onp.asarray(valid.asnumpy()).astype(bool))


def test_logits_match_the_reference(system_and_reference):
    s = system_and_reference
    keep = s["valid"]
    onp.testing.assert_allclose(s["logits"][keep], onp.asarray(s["r_out"]["logits"])[keep],
                                rtol=2e-4, atol=2e-5)
    assert len(s["r_out"]["routes"]) == 4          # one dense layer, four MoE layers


def test_loss_matches_the_reference(system_and_reference):
    s = system_and_reference
    assert s["loss"] == pytest.approx(s["r_loss"], rel=1e-5)
    assert abs(s["r_loss"] - onp.log(CFG["vocab_size"])) < 0.5     # random weights: ln V


def test_parameter_gradients_match_the_reference(system_and_reference):
    s = system_and_reference
    assert set(s["grads"]) == set(s["r_grads"])
    for name, want in s["r_grads"].items():
        want, got = onp.asarray(want), onp.asarray(s["grads"][name])
        if name.endswith(("expert_bias", "expert_rows")):   # buffers outside the gradient
            assert not got.any() and not want.any(), name
            continue
        assert onp.abs(want).max() > 0, name
        onp.testing.assert_allclose(got, want, rtol=5e-3, atol=2e-6 + 2e-3 * onp.abs(want).max(),
                                    err_msg=name)


def test_trains_through_sharded_trainer_in_one_compiled_step(system_and_reference):
    trainer = system_and_reference["trainer"]
    batch = _batch()
    losses = [float(trainer.step(*batch).asnumpy()) for _ in range(3)]
    assert trainer.last_path == "pjit" and trainer._step_fn._cache_size() == 1
    assert onp.isfinite(losses).all() and losses[-1] < losses[0]


# --- the share of a deployment ------------------------------------------------

def _moe_layer_params(rng, E, num_shared=1):
    C, F = CFG["hidden_size"], CFG["moe_intermediate_size"]
    n = lambda *s: jnp.asarray(rng.normal(0, 0.1, s), jnp.float32)  # noqa: E731
    return {"router_weight": n(E, C), "expert_bias": jnp.zeros((E,)),
            "experts_w13": n(E, 2 * F, C), "experts_w2": n(E, C, F),
            "shared_gate_weight": n(num_shared * F, C), "shared_up_weight": n(num_shared * F, C),
            "shared_down_weight": n(C, num_shared * F)}


@pytest.fixture
def pallas_gmm(monkeypatch):
    """The Pallas kernels (interpret mode here) where ``auto`` would take
    ``ragged_dot`` off the chip."""
    monkeypatch.setattr(moe_gmm, "grouped_matmul",
                        functools.partial(moe_gmm.grouped_matmul, impl="pallas"))


# experts, experts a token, shares, shared experts, scale: Trinity-Mini's
# shape (two shares of 4 of 8, top 2, one shared expert) and Kanana-2's
# (eight shares of 2 of 16, top 6, two shared experts as one FFN, 2.448)
@pytest.mark.parametrize("E,k,shares,num_shared,scale", [
    pytest.param(8, 2, 2, 1, 2.826, id="top2_of_8_two_shares"),
    pytest.param(16, 6, 8, 2, 2.448, id="top6_of_16_eight_shares_two_shared"),
])
def test_shares_add_up_to_the_uncut_layer(pallas_gmm, E, k, shares, num_shared, scale):
    """Over all the shares of the experts, the routed parts added and the
    shared experts counted once equal the reference's whole layer."""
    rng = onp.random.default_rng(1)
    T, n = 40, E // shares
    cfg = dict(CFG, num_experts=E, num_experts_per_tok=k, route_scale=scale)
    p = _moe_layer_params(rng, E, num_shared)
    x = jnp.asarray(rng.normal(0, 1, (T, CFG["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference.moe(p, "", cfg, x, (0, E))
        shared = reference._gated(x, p["shared_gate_weight"], p["shared_up_weight"],
                                  p["shared_down_weight"])
    idx, w = moe_dropless.sigmoid_topk(x, p["router_weight"], p["expert_bias"], k,
                                       True, scale)
    parts = [moe_dropless.routed_experts(x, idx, w, p["experts_w13"][f:f + n],
                                         p["experts_w2"][f:f + n], (f, n), tile_rows=8)
             for f in range(0, E, n)]
    onp.testing.assert_allclose(shared + sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert all(float(jnp.abs(part).max()) > 0 for part in parts)


@pytest.fixture
def row_kernels(monkeypatch):
    """The path a TPU takes (``moe_rows`` kernels and the Pallas grouped
    matmuls, all in interpret mode here) where the platform would choose
    XLA's gathers and ``ragged_dot``."""
    monkeypatch.setattr(moe_dropless, "_row_kernels", lambda x: True)


_SKEWS = ["all_on_one_held_expert", "all_held", "none_held"]


@pytest.mark.parametrize("passes", ["xla", "row_kernels"])
@pytest.mark.parametrize("skew", _SKEWS)
def test_no_assignment_is_dropped_whatever_the_skew(skew, passes, request):
    """Every token picks the same experts: the buffer for the worst case takes
    them all, and the result is still the reference's (``all_held`` fills 14
    of the buffer's 16 tiles)."""
    if passes == "row_kernels":
        request.getfixturevalue("row_kernels")
    rng = onp.random.default_rng(2)
    E, k, T, held = 8, 2, 48, (4, 4)
    p = _moe_layer_params(rng, E)
    x = jnp.asarray(rng.normal(0, 1, (T, CFG["hidden_size"])), jnp.float32)
    picked = {"all_on_one_held_expert": [5, 0], "all_held": [4, 7], "none_held": [0, 3]}[skew]
    idx = jnp.tile(jnp.asarray(picked, jnp.int32), (T, 1))
    w = jnp.asarray(rng.uniform(0.2, 1.0, (T, k)), jnp.float32)
    n_held = sum(held[0] <= e < held[0] + held[1] for e in picked) * T
    place = moe_dropless.placement(idx, held, 8)
    assert int(place["assignments_held"]) == int(place["rows_placed"]) == n_held
    assert int(place["counts"].sum()) == n_held
    cot = jnp.asarray(rng.normal(0, 1, x.shape), jnp.float32)

    def program(x, w, w13, w2):
        return moe_dropless.routed_experts(x, idx, w, w13, w2, held, tile_rows=8)

    def plain(x, w, w13, w2):
        out = jnp.zeros_like(x)
        with jax.default_matmul_precision("highest"):
            for slot, e in enumerate(picked):
                if held[0] <= e < held[0] + held[1]:
                    gate, up = jnp.split(w13[e - held[0]], 2, axis=0)
                    out = out + w[:, slot, None] * reference._gated(x, gate, up, w2[e - held[0]])
        return out

    args = (x, w, p["experts_w13"][4:], p["experts_w2"][4:])
    got, got_vjp = jax.vjp(program, *args)
    want, want_vjp = jax.vjp(plain, *args)
    onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    for a, b in zip(got_vjp(cot), want_vjp(cot)):        # through the gathers' own VJPs
        onp.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


# --- the row kernels against the XLA passes they replace on a TPU -------------------

def _routed_case(skew, dtype=jnp.float32, T=48):
    """``(idx, args, held)`` of one routed half at ``tile_rows=8``: the three
    skews above, or each token's two experts drawn apart."""
    rng = onp.random.default_rng(7)
    E, k, held = 8, 2, (4, 4)
    p = _moe_layer_params(rng, E)
    x = jnp.asarray(rng.normal(0, 1, (T, CFG["hidden_size"])), dtype)
    if skew == "drawn":
        idx = jnp.asarray(onp.stack([rng.permutation(E)[:k] for _ in range(T)]), jnp.int32)
    else:
        picked = {"all_on_one_held_expert": [5, 0], "all_held": [4, 7], "none_held": [0, 3]}[skew]
        idx = jnp.tile(jnp.asarray(picked, jnp.int32), (T, 1))
    w = jnp.asarray(rng.uniform(0.2, 1.0, (T, k)), jnp.float32)
    return idx, (x, w, p["experts_w13"][4:].astype(dtype), p["experts_w2"][4:].astype(dtype)), held


def _value_and_grads(idx, args, held):
    def f(x, w, w13, w2):
        return moe_dropless.routed_experts(x, idx, w, w13, w2, held, tile_rows=8)
    cot = jnp.asarray(onp.random.default_rng(8).normal(0, 1, args[0].shape), args[0].dtype)
    out, vjp = jax.vjp(f, *args)
    return (out,) + vjp(cot)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("skew", _SKEWS + ["drawn"])
def test_row_kernels_give_what_the_xla_passes_give(skew, dtype, monkeypatch):
    """The routed half by ``moe_rows`` and the Pallas grouped matmuls against
    the same half by XLA's gathers over the whole buffer: the output and the
    gradients of ``x``, the routing weights and both expert matrices."""
    idx, args, held = _routed_case(skew, jnp.dtype(dtype))
    monkeypatch.setattr(moe_gmm, "grouped_matmul",      # one product under both
                        functools.partial(moe_gmm.grouped_matmul, impl="pallas"))
    want = _value_and_grads(idx, args, held)
    monkeypatch.setattr(moe_dropless, "_row_kernels", lambda x: True)
    got = _value_and_grads(idx, args, held)
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" else dict(rtol=2e-2, atol=2e-2)
    for name, a, b in zip(("out", "dx", "dweight", "dw13", "dw2"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        onp.testing.assert_allclose(onp.asarray(a, "float32"), onp.asarray(b, "float32"),
                                    err_msg=name, **tol)


@pytest.mark.parametrize("skew", ["all_on_one_held_expert", "drawn"])
def test_no_pass_reads_a_dead_tile(skew, row_kernels):
    """Rows at or past ``rows_padded`` of every intermediate buffer (the
    gathered rows, both products, the gate, their gradients, the slabs) set
    to NaN as each kernel hands them over: the output and every gradient
    are finite and the very values of the unpoisoned run."""
    idx, args, held = _routed_case(skew)
    clean = _value_and_grads(idx, args, held)
    tiles = moe_dropless.buffer_rows(idx.shape[0], idx.shape[1], held[1], 8) // 8
    plan = moe_dropless.plan_rows(idx, held, 8)
    assert int(plan.rows_padded) // 8 < tiles               # there are dead tiles to poison
    with probe.dead_tiles_poisoned(tiles) as poisoned:
        slab = moe_rows.pack(jnp.ones((tiles * 8, 16)), jnp.asarray([1], jnp.int32), 8)
        assert int(slab[8 * 8, 0]) == 0xFFFFFFFF            # the poison is in place,
        del poisoned[:]
        dirty = _value_and_grads(idx, args, held)
    assert {"pack", "gather", "_by_tile", "gmm"} <= set(poisoned)   # and the half ran on it
    for name, a, b in zip(("out", "dx", "dweight", "dw13", "dw2"), dirty, clean):
        assert onp.isfinite(onp.asarray(a)).all(), name
        onp.testing.assert_array_equal(onp.asarray(a), onp.asarray(b), err_msg=name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_slab_gives_its_rows_back_bit_for_bit(dtype):
    """``pack`` then ``gather``: the rows named, in order, under the valid
    rows of the tiles in use; zero in a tile's padding rows."""
    rng = onp.random.default_rng(9)
    dt, N, C, tm = jnp.dtype(dtype), 48, 64, 8
    a = jnp.asarray(rng.normal(0, 1, (N, C)), dt)
    idx = jnp.asarray(rng.integers(0, N, (10 * tm,)), jnp.int32)
    tile_valid = jnp.asarray([8, 3, 0, 5] + [0] * 6, jnp.int32)
    out = moe_rows.gather(moe_rows.pack(a, None, tm), (C, dt), idx, tile_valid,
                          jnp.asarray([4], jnp.int32), tm)
    live = (jnp.arange(10 * tm) % tm < jnp.repeat(tile_valid, tm))[:, None]
    onp.testing.assert_array_equal(onp.asarray(out[:4 * tm], "float32"),
                                   onp.asarray(jnp.where(live, a[idx], 0)[:4 * tm], "float32"))


def test_the_live_share_of_the_buffer_is_counted(system_and_reference):
    """``rows_padded`` over the buffer's rows, from the plan the layer makes:
    in ``placement`` and as a gauge beside the other routing counters."""
    from incubator_mxnet_tpu.telemetry import metrics
    idx, _, held = _routed_case("all_on_one_held_expert")
    place = moe_dropless.placement(idx, held, 8)
    # 48 rows on one expert (6 tiles) and an empty tile for each of the other three
    assert float(place["rows_live_share"]) == pytest.approx(9 * 8 / 128)
    routes = system_and_reference["net"].routing(*system_and_reference["args"])
    for i, r in enumerate(routes):
        share = metrics.gauge("mxtpu_moe_rows_live_share", layer=str(i)).value
        assert 0 < share <= 1 and share == pytest.approx(float(r["rows_live_share"]))


@pytest.mark.parametrize("remat", [False, True], ids=["held", "recomputed"])
def test_the_compiled_step_counts_its_own_rows(remat):
    """Each MoE layer's ``expert_rows`` buffer after a step holds what the
    layer's own routing function counts on that batch with those weights
    (rate 0: the step moves nothing), through a recomputed layer too."""
    net = _net(remat=remat)
    ids, pos, vl, lab = _batch(seed=5)
    trainer = parallel.ShardedTrainer(
        net, models.afmoe_lm_loss, "sgd", dict(learning_rate=0.0),
        mesh=parallel.make_mesh(devices=jax.devices()[:1]), n_labels=1)
    trainer.step(ids, pos, vl, lab)
    stepped = [onp.asarray(r) for r in net.expert_rows()]
    routes = net.routing(*(mx.nd.array(a, dtype=a.dtype) for a in (ids, pos, vl)), publish=False)
    assert len(stepped) == len(routes) == 4
    for got, r in zip(stepped, routes):
        onp.testing.assert_array_equal(got, onp.asarray(r["counts"]))
        assert got.sum() == int(r["assignments_held"]) > 0


def test_routing_counters_are_published(system_and_reference):
    from incubator_mxnet_tpu.telemetry import metrics
    routes = system_and_reference["net"].routing(*system_and_reference["args"])
    assert len(routes) == 4
    for r in routes:
        assert r["idx"].shape == (B * L, 2)
        assert int(r["assignments_held"]) == int(r["rows_placed"]) == int(r["counts"].sum())
    text = metrics.prometheus_text()
    for name in ("assignments_held", "assignments_dropped", "expert_rows_max", "expert_rows_mean"):
        assert f"mxtpu_moe_{name}" in text
    assert metrics.gauge("mxtpu_moe_assignments_dropped", layer="0").value == 0


def test_release_block_frees_the_blocks_copies_and_sync_restores_them(system_and_reference):
    """After the first step the trainer's copies are the weights; the block's
    second set can be given back to the device and fetched again."""
    trainer, net = system_and_reference["trainer"], system_and_reference["net"]
    trainer.release_block()
    assert all(a._data.is_deleted() for p in net.collect_params().values()
               for a in p._data.values())
    assert onp.isfinite(float(trainer.step(*_batch()).asnumpy()))
    trainer.sync_to_block()
    logits, _ = net(*system_and_reference["args"])
    assert onp.isfinite(logits.asnumpy()).all()


# --- grouped matmul over ragged groups ------------------------------------------

@pytest.mark.parametrize("sizes", [(5, 0, 17, 8), (0, 0, 0, 3), (16, 16, 16, 16)],
                         ids=["ragged_with_an_empty_group", "nearly_empty", "whole_tiles"])
def test_grouped_matmul_values_and_gradients(sizes):
    rng = onp.random.default_rng(4)
    tm, K, N, G = 8, 32, 48, len(sizes)
    padded = [max(-(-s // tm), 1) * tm for s in sizes]
    R = 12 * tm                          # one buffer for every case: tiles out of use at its end
    lhs = onp.zeros((R, K), "float32")
    tile_group, row = [], 0
    for g, (s, ps) in enumerate(zip(sizes, padded)):
        lhs[row:row + s] = rng.normal(0, 1, (s, K))
        tile_group += [g] * (ps // tm)
        row += ps
    n_tiles = jnp.asarray([len(tile_group)], jnp.int32)
    tile_group = jnp.asarray(tile_group + [G - 1] * (R // tm - len(tile_group)), jnp.int32)
    rhs = jnp.asarray(rng.normal(0, 1, (G, N, K)), jnp.float32)
    lhs = jnp.asarray(lhs)
    live = (jnp.arange(R) < row)[:, None]
    group_of_row = jnp.repeat(tile_group, tm)

    def kernel(lhs, rhs):
        return jnp.where(live, moe_gmm.grouped_matmul(lhs, rhs, tile_group, n_tiles, tm,
                                                      impl="pallas"), 0)

    def ragged(lhs, rhs):
        return moe_gmm.grouped_matmul(lhs, rhs, tile_group, n_tiles, tm, impl="ragged")

    def plain(lhs, rhs):
        with jax.default_matmul_precision("highest"):
            return jnp.where(live, jnp.einsum("rk,rnk->rn", lhs, rhs[group_of_row]), 0)

    cot = jnp.asarray(rng.normal(0, 1, (R, N)), jnp.float32)
    got, got_vjp = jax.vjp(kernel, lhs, rhs)
    want, want_vjp = jax.vjp(plain, lhs, rhs)
    onp.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    onp.testing.assert_allclose(ragged(lhs, rhs), want, rtol=1e-4, atol=1e-4)
    for a, b in zip(got_vjp(cot), want_vjp(cot)):
        onp.testing.assert_allclose(a[:row] if a.shape[0] == R else a,
                                    b[:row] if b.shape[0] == R else b, rtol=1e-4, atol=1e-4)


# --- grouped K/V heads in the flash kernels -------------------------------------

@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_BQ", "64")
    monkeypatch.setenv("MXTPU_FLASH_BK", "64")


@pytest.mark.parametrize("causal,window,masked", [
    (True, 96, True), (True, None, True), (False, None, False)],
    ids=["window_mask", "causal_mask", "plain"])
def test_flash_grouped_heads_against_the_xla_path(small_tiles, causal, window, masked):
    """Interpret mode, q (2, 4, 256, 32) over k, v (2, 2, 256, 32): values and
    the gradients of q, k and v (dk, dv summed over each group's two heads)."""
    Bq, H, Hkv, Lq, D = 2, 4, 2, 256, 32
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (Bq, H, Lq, D))
    k, v = (jax.random.normal(kk, (Bq, Hkv, Lq, D)) for kk in keys[1:3])
    cot = jax.random.normal(keys[3], (Bq, H, Lq, D))
    mask = (jnp.arange(Lq)[None, :] < jnp.array([Lq, Lq * 3 // 4])[:, None]) if masked else None

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, causal=causal, window=window)

    def xla(q, k, v):
        return dot_product_attention(q, k, v, mask=None if mask is None else mask[:, None, None],
                                     causal=causal, window=window, impl="xla")

    got, got_vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(xla, q, k, v)
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for a, b in zip(got_vjp(cot), want_vjp(cot)):
        assert a.shape == b.shape
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_flash_supported_knows_the_grouped_shapes(monkeypatch):
    monkeypatch.setattr(fa, "_interpret_for", lambda x: False)      # as on the chip
    q = jnp.zeros((2, 32, 512, 128), jnp.bfloat16)
    kv = lambda h: jnp.zeros((2, h, 512, 128), jnp.bfloat16)        # noqa: E731
    assert fa.flash_supported(q, kv(4), kv(4)) and fa.flash_supported(q, kv(32), kv(32))
    assert not fa.flash_supported(q, kv(5), kv(5))                   # 32 is no multiple of 5
    assert not fa.flash_supported(q, kv(4), kv(8))
    assert fa._kernel_name("flash_fwd", None) == "flash_fwd"
    assert fa._kernel_name("flash_bwd_dkv", 2048) == "flash_bwd_dkv_win"


def test_rotary_is_a_rotation_by_relative_position():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    r0, r3 = models.afmoe.rotary(x, pos, 10000.0), models.afmoe.rotary(x, pos + 3, 10000.0)
    onp.testing.assert_allclose(jnp.linalg.norm(r0, axis=-1), jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # q.k depends on the distance only
    onp.testing.assert_allclose(jnp.einsum("blhd,bmhd->blmh", r0, r0),
                                jnp.einsum("blhd,bmhd->blmh", r3, r3), rtol=1e-4, atol=1e-4)
    onp.testing.assert_allclose(r0, reference._rotary(x, pos, 10000.0), rtol=1e-6, atol=1e-6)
