"""What the per-architecture test files (``test_olmoe``,
``test_smallthinker``, ``test_kanana2``, ``test_kimi_linear``,
``test_trinity_mini``, ``test_qwen3_next``) share: how a file draws its
small model, and ONE compile a (function, configuration) for the life of
the process. Each file keeps its own ``small()``: its widths are the
file's content.

Why the compiles are shared: the seconds of these files are XLA compiling
the same small model again (ROADMAP C11 (c)). A ``TransformerConfig`` is
hashable and already a static argument of every call, so a case that
differs from another in the seed alone, and a case of another test on the
same configuration, runs the executable the first one built. A
configuration that differs in one field is another key and compiles, as a
case whose configuration IS what it tests has to. jax keys its own cache
on the trace's context too, so a call under
``jax.default_matmul_precision("highest")`` never runs an executable
traced outside one. What a test patches (``monkeypatch.setattr`` on the
program's modules) is NOT in any key: a case that patches the program
compiles its own ``jax.jit``, never one of these.
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import optax
import pytest

from ray_tpu import models
from ray_tpu.ops import moe

SCALE = 5.0             # x the program's N(0, 0.02): every branch moves
ROUTER_SCALE = 10.0     # x that again: routing is uneven


@pytest.fixture(autouse=True)
def highest_precision():
    """A file that imports this runs every case under ``highest``: one
    context a file is one compile a key (the CPU computes float32
    products in float32 under any)."""
    with jax.default_matmul_precision("highest"):
        yield


@functools.cache
def jitted(fn, *static):
    """``lambda *arrays: fn(*arrays, *static)`` under ``jax.jit``: the SAME
    callable, and so the same executables, for an equal (function, static
    arguments)."""
    return jax.jit(lambda *arrays: fn(*arrays, *static))


def value_and_grad(fn, *static, has_aux: bool = False):
    """``jax.value_and_grad`` of ``fn(params, *arrays, *static)`` by
    ``params``, jitted once a key as ``jitted`` is."""
    return _value_and_grad(fn, static, has_aux)


@functools.cache
def _value_and_grad(fn, static, has_aux):
    return jax.jit(jax.value_and_grad(
        lambda *arrays: fn(*arrays, *static), has_aux=has_aux))


def grad(fn, *static):
    """The gradient alone, from ``value_and_grad``'s compile."""
    both = value_and_grad(fn, *static)
    return lambda *arrays: both(*arrays)[1]


def forward(params, tokens, cfg):
    """The program's logits."""
    return jitted(models.forward, cfg)(params, tokens)


def _lm_loss(params, rows, cfg):
    return models.lm_loss(params, {"tokens": rows}, cfg)


def lm_loss(params, rows, cfg):
    """The program's (loss, metrics) on ``rows`` [B, T + 1]."""
    return jitted(_lm_loss, cfg)(params, rows)


def loss(params, rows, cfg):
    """The program's whole training loss, from ``lm_loss``'s compile."""
    return lm_loss(params, rows, cfg)[0]


def program_loss(params, rows, cfg):
    """The same loss, plain: for ``jax.grad``, and for a patched program,
    which none of the compiles here may stand in for."""
    return _lm_loss(params, rows, cfg)[0]


def loss_metrics_and_grads(params, rows, cfg):
    """((loss, metrics), gradients) of the program from ONE compile."""
    return value_and_grad(_lm_loss, cfg, has_aux=True)(params, rows)


# -- drawing a small model ------------------------------------------------------

def scaled(params, *, scale=SCALE, router_scale=ROUTER_SCALE, bias_scale=1.0,
           as_drawn=("ln1", "ln2")):
    """``params`` with every leaf of its layer stacks at ``scale`` x its
    draw, but the leaves under a name in ``as_drawn``; the router's weight
    at ``router_scale`` x that again, its bias (where there is one) at
    ``bias_scale`` x."""
    def one(path, a):
        return a if {k.key for k in path} & set(as_drawn) else a * scale

    out = dict(params)
    for stack in ("layers", "dense_layers"):
        if stack in params:
            out[stack] = jax.tree_util.tree_map_with_path(one, params[stack])
    router = dict(out["layers"]["router"])
    router["w"] = router["w"] * router_scale
    if "b" in router:
        router["b"] = router["b"] * bias_scale
    out["layers"] = dict(out["layers"], router=router)
    return out


def make(small, seed: int = 0, *, tokens: int | None = None,
         init=models.init_params, **kw):
    """(cfg, params, rows [2, ``tokens`` + 1]) of the file's ``small``
    model: ``init(key of seed, cfg)``, ``scaled`` (its arguments among
    ``kw``; every other one is the configuration's), rows of ``seed`` +
    1000. ``init_params`` op by op unless the file says otherwise: jax
    compiles a draw once a leaf's shape, which the configurations of a
    file mostly share, where a jitted ``init_params`` compiles for seconds
    a configuration (a file with few configurations and many kinds of
    leaf, Kimi Linear's, gives its jitted one)."""
    scaling = {name: kw.pop(name) for name in
               ("scale", "router_scale", "bias_scale", "as_drawn")
               if name in kw}
    cfg = small(**kw)
    params = scaled(init(jax.random.PRNGKey(seed), cfg), **scaling)
    rows = jax.random.randint(
        jax.random.PRNGKey(seed + 1000),
        (2, (cfg.max_seq_len if tokens is None else tokens) + 1), 0,
        cfg.vocab_size)
    return cfg, params, rows


# -- an expert layer cut into ranks' shares -------------------------------------------

def ranks_parts_sum_to_the_uncut_layer(x, lp, cfg, ranks, reference_layer,
                                       one_layer, tol):
    """One expert layer ``lp`` (all experts) on ``x``: ``one_layer(x, lp,
    cfg)``, the program's block, as each of ``ranks`` ranks runs it on its
    share of the experts, less what every rank computes alike, is that
    rank's routed part; ``alike`` counted ONCE, the parts sum to the uncut
    ``reference_layer(x, lp)``, and the program that holds every expert is
    that layer too. Returns ``alike``: the reference's layer with the
    routed experts' output zeroed."""
    uncut = reference_layer(x, lp)
    alike = reference_layer(x, dict(lp, mlp=dict(
        lp["mlp"], w_down=lp["mlp"]["w_down"] * 0)))
    parts = []
    for rank in range(ranks):
        first, end = moe.held_range(cfg.n_experts, rank, ranks)
        mlp = {name: (w[first:end] if name.startswith("w_") else w)
               for name, w in lp["mlp"].items()}
        y_r = one_layer(x, dict(lp, mlp=mlp),
                        replace(cfg, experts_held=(rank, ranks)))
        parts.append(y_r - alike)
    assert all(float(jnp.abs(p).max()) > 1000 * tol for p in parts)
    assert float(jnp.abs(alike + sum(parts) - uncut).max()) < 5 * tol
    assert float(jnp.abs(one_layer(x, lp, cfg) - uncut).max()) < 5 * tol
    return alike


# -- partitioning -------------------------------------------------------------------

def sharded_loss_is_the_unsharded(cfg, params, rows, tol):
    """``models.partition_specs(cfg)`` (returned, for the file's own
    assertions) name exactly ``params``' leaves, and the loss of rows and
    their mirror on a (data 2, fsdp 2, tensor 2) mesh of the CPU's virtual
    devices, the parameters placed by those specs, is the unsharded one.
    Returns (specs, the placed parameters)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import (MeshConfig, batch_sharding,
                                  infer_param_specs, make_shardings)

    specs = models.partition_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda s: s is None or isinstance(
        s, P)) == jax.tree.structure(jax.tree.map(lambda a: None, params),
                                     is_leaf=lambda s: s is None)
    mesh = MeshConfig(data=2, fsdp=2, tensor=2).build()
    shardings = make_shardings(mesh, infer_param_specs(params, mesh, specs))
    placed = jax.tree.map(jax.device_put, params, shardings)
    rows4 = jnp.concatenate([rows, rows[::-1]], 0)
    want = lm_loss(params, rows4, cfg)[0]
    got = jax.jit(lambda p, r: models.lm_loss(p, {"tokens": r}, cfg,
                                              mesh=mesh)[0])(
        placed, jax.device_put(rows4, batch_sharding(mesh)))
    assert abs(float(got) - float(want)) <= tol
    return specs, placed


# -- a train step ----------------------------------------------------------------

def adamw(lr: float, weight_decay: float = 1e-4):
    """ONE optimizer a (rate, decay): ``train_step``'s key."""
    return _adamw(lr, weight_decay)


def train_step(cfg, opt, accum_steps: int = 1):
    """``models.make_train_step`` jitted once a (configuration, optimizer,
    accumulation): call it, or ``.lower(...)`` it for its text."""
    return _train_step(cfg, opt, accum_steps)


# behind the two above: ``functools.cache`` keys a default that is left
# out and the same value given apart
@functools.cache
def _adamw(lr, weight_decay):
    return optax.adamw(lr, weight_decay=weight_decay)


@functools.cache
def _train_step(cfg, opt, accum_steps):
    return jax.jit(models.make_train_step(cfg, opt, accum_steps=accum_steps))


def train_state(params, opt):
    return {"params": params, "opt_state": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}
