"""``chip_smoke.py`` off the chip: it must fail, and say nothing that could
be read as a pass. What it proves on the chip only a chip run shows
(``chiprun -- python chip_smoke.py``; results in CHANGES.md).

Each case runs the script as a CPU-pinned child, as the driver does: the
script turns the persistent compilation cache on for its process, which
must not leak into a pytest worker."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SUCCESS = '"ok": true'


def _run(tmp_path, *args, cwd=REPO, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)     # the script finds the repo beside it
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"),
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=600)


def test_rehearsal_runs_every_phase_and_cannot_pass(tmp_path):
    """The whole control flow at ``bert_2_128_2`` on the CPU: every phase
    holds, and the verdict is still ok=false, exit 3, platform cpu."""
    proc = _run(tmp_path, "--rehearse")
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert _SUCCESS not in proc.stdout
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    assert [ln["phase"] for ln in lines[:-1]] == [
        "device", "cache", "kernel", "kernel", "train", "sync",
        "checkpoint", "serve", "done"]
    last = lines[-1]
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    by_phase = {ln["phase"]: ln for ln in lines[:-1]}
    assert by_phase["cache"]["dir"] == str(tmp_path / "jax_cache")
    assert by_phase["cache"]["from_env"] is True
    train = by_phase["train"]
    assert train["losses"][-1] < train["losses"][0]
    assert train["step_traces"] == 1 and train["path"] == "pjit"
    # every donated byte (params, masters, moments) aliases its output
    assert train["aliased_bytes"] >= train["donated_bytes"]
    assert by_phase["serve"]["cache_info"]["post_warmup_compiles"] == 0
    assert by_phase["checkpoint"]["state_identical"] is True


@pytest.mark.parametrize("args", [(), ("--chips", "4")],
                         ids=["one_chip", "four_chips"])
def test_refuses_the_cpu(tmp_path, args):
    """As the driver runs it, in a sandbox with no accelerator: non-zero,
    the platform named, no result line, no cache directory made."""
    proc = _run(tmp_path, *args)
    assert proc.returncode not in (0, 3)
    assert proc.stdout == ""
    assert "cpu" in proc.stderr and "TPU" in proc.stderr
    assert not (tmp_path / "jax_cache").exists()


def test_fails_alone_in_a_directory(tmp_path):
    """The script without the program proves nothing and must not pass."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    proc = _run(tmp_path, "--rehearse", cwd=str(alone),
                script=str(alone / "chip_smoke.py"))
    assert proc.returncode not in (0, 3)
    assert _SUCCESS not in proc.stdout and '"ok"' not in proc.stdout
    assert "ModuleNotFoundError" in proc.stderr


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_bert_trainer_is_the_bf16_job_with_fp32_masters(remat,
                                                        load_script):
    """The job every chip phase trains, at ``bert_2_128_2``: bf16
    weights, an fp32 master first in each one's optimizer state,
    one finite compiled step; ``remat=`` reaches ``get_bert`` (the fork a
    BERT-large run needs to fit must not be first exercised there)."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from incubator_mxnet_tpu import parallel
    # importing it starts no backend and makes no cache: main() does both
    smoke = load_script("chip_smoke.py")
    overrides = {"remat": True} if remat else {}
    net, trainer = smoke.bert_trainer(
        "bert_2_128_2", 64, parallel.make_mesh(devices=jax.devices()[:1]),
        **overrides)
    assert net.encoder._remat is remat
    loss = float(trainer.step(*smoke.bert_batch(2, 64)).asnumpy())
    assert onp.isfinite(loss)
    assert trainer.last_path == "pjit" and trainer.last_step_graphs == 1
    names = sorted(net.collect_params())
    half = [(n, v, s) for n, v, s in zip(names, trainer._param_vals,
                                         trainer._opt_states)
            if v.dtype == jnp.bfloat16]
    # every matmul weight is bf16 (layer norms keep fp32 scales, PR 26)
    assert {n for n in names if n.endswith("weight")} \
        <= {n for n, _, _ in half}
    assert all(s[0].dtype == jnp.float32 and s[0].shape == v.shape
               for _, v, s in half)
