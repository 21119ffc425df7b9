"""What decides a train cell's ``correct``, through the real entry point
at test size on fake chips (``_tinycells``; CPU, never a speed): the
timed path broken underneath makes ``correct`` false, by the check that
is there to catch it, and ``python -m chipbench.reference.compare``
reproduces a cell run's statistics because it calls the same function.

Each broken driver is a COPY's ``drivers/train_job.py`` with one line
changed; the repository's own file has no switch for any of this.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import _tinycells
from test_chipbench_drivers_cpu import _run

DRIVER = "chipbench/drivers/train_job.py"
# one line of the driver -> the same line with one thing wrong
BREAKS = {
    # the forward that is compared runs on weights whose layer 1 adds
    # nothing; the step trains the sound ones
    "compared_forward_drops_a_layer": (
        "            params, jnp.asarray(sample),",
        "            _common.apply_control('drop_layer=1', params)[0], "
        "jnp.asarray(sample),"),
    # the step is built from another configuration than the forward that
    # was compared: its loss has no router term
    "step_of_another_configuration": (
        "    step = jax.jit(models.make_train_step(cfg, opt, mesh=mesh),",
        "    step = jax.jit(models.make_train_step(spec.model_config("
        "cfg_data, router_aux_weight=0.0), opt, mesh=mesh),"),
    # the step returns its state unchanged: nothing is learnt
    "step_returns_its_state_unchanged": (
        "        state, metrics = step(state, {\"tokens\": batch[\"tokens\"]})\n"
        "        losses.append(float(metrics[\"loss\"]))",
        "        _, metrics = step(jax.tree.map(jnp.copy, state), "
        "{\"tokens\": batch[\"tokens\"]})\n"
        "        losses.append(float(metrics[\"loss\"]))"),
}


def _broken_root(tmp: str, fault: str) -> str:
    root = _tinycells.make_root(tmp)
    path = os.path.join(root, DRIVER)
    with open(path) as f:
        text = f.read()
    old, new = BREAKS[fault]
    assert text.count(old) == 1, (fault, text.count(old))
    with open(path, "w") as f:
        f.write(text.replace(old, new))
    return root


def _failed(res: dict) -> list[str]:
    return [n.split()[1].rstrip(":") for n in res["notes"]
            if n.startswith("check ") and n.endswith(": FAILED")]


def test_a_dropped_layer_under_the_compared_forward_is_not_correct(tmp_path):
    root = _broken_root(str(tmp_path), "compared_forward_drops_a_layer")
    res = _run(root, "tiny-moe-train", 0, 2.0, seed=31)
    assert res["correct"] is False
    assert "program_agrees_with_reference" in _failed(res), res["notes"]
    outside = [n for n in res["notes"] if n.startswith("compared ")
               and n.endswith("OUTSIDE")]
    assert any(n.startswith("compared logit_rel_d ") for n in outside), outside


def test_a_step_of_another_configuration_is_not_the_compared_forward(tmp_path):
    root = _broken_root(str(tmp_path), "step_of_another_configuration")
    res = _run(root, "tiny-moe-train", 0, 2.0, seed=33)
    assert res["correct"] is False
    # the forward agrees with its reference; the STEP is not that forward:
    # its first loss lacks the router term, 0.02 against a limit of 1e-3
    assert _failed(res) == ["first_step_is_the_compared_forward"], res["notes"]
    line = next(n for n in res["notes"] if n.startswith("compared first_step_d"))
    assert 0.019 < float(line.split()[2]) < 0.0215, line


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    root = _broken_root(str(tmp_path), "step_returns_its_state_unchanged")
    res = _run(root, "tiny-train", 0, 2.0, seed=37)
    assert res["correct"] is False
    # every forward is sound and the first loss is the compared one; the
    # weights did not move by the learning rate, they did not move at all
    assert _failed(res) == ["step_moves_the_weights"], res["notes"]
    line = next(n for n in res["notes"] if n.startswith("compared weights_moved"))
    assert float(line.split()[2]) == 0.0, line


def test_reference_compare_runs_the_drivers_comparison(tmp_path):
    """Same seed, same rows, same weights: the hand tool prints the
    statistics the cell run recorded, because both call
    ``_common.agreement`` with the driver's own ``program_side``."""
    from chipbench.drivers import train_job
    from chipbench.reference import _common, compare

    assert compare.__doc__ and "_common.agreement" in compare.__doc__
    root = _tinycells.make_root(str(tmp_path))
    res = _run(root, "tiny-moe-train", 0, 2.0, seed=35)
    assert res["correct"] is True, res["notes"]
    with open(os.path.join(root, ".chipbench_cache",
                           "last-run-tiny-moe-train-t0.json")) as f:
        recorded = json.load(f)["train"]["agreement"]
    env = dict(os.environ, PYTHONPATH=_tinycells.REPO,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.reference.compare", "--config",
         "tiny-moe-train", "--seed", "35", "--rows", "2", "--seq-len", "32",
         "--control", "sound", "--control", "drop_layer=0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    sound, dropped = (json.loads(ln) for ln in proc.stdout.splitlines()
                      if ln.startswith("{"))
    for name in _common.RECORDED:
        assert sound[name] == pytest.approx(recorded[name], rel=1e-6,
                                            abs=1e-9), name
    assert sound["outside"] == [] and sound["limits"] == {
        k: list(v) for k, v in _common.LIMITS.items()}
    assert "logit_rel_d" in dropped["outside"]
    assert train_job.program_side.__module__ == "chipbench.drivers.train_job"
