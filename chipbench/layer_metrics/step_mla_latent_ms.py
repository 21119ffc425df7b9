"""Model step: device time of the leaf instructions under the scope
``mla_latent`` (what latent attention adds outside the kernels: the
down-projection to the latent and the one rotary key, the latent's norm,
the up-projection to every head's key and value, RoPE on the rotary
parts, whatever lays the kernels' operands out; every pass), a run of
``jit_train_step`` in the traced window, mean over the chips
(``_named_scope``)."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "mla_latent")
