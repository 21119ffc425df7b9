"""Model step: device time of the leaf instructions under ``ssm_carry``,
the scope ``ray_tpu/ops/state_space.py`` opens inside ``attn_linear`` /
``attn_core`` around the chunk-to-chunk CARRY of the state-space scan: the
``seq_len / chunk`` sequential steps a layer and pass (forward, again in
the layer's recompute, and turned round in the backward), each an
elementwise update of the [heads, channels, state] state: what of
``step_kda_core_ms`` is bound by latency and not by the MXU (the rest is
the chunks' batched matmuls). None on a program, or a model, without the
scope. Read by ``_named_scope``."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "ssm_carry")
