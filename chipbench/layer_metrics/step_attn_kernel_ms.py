"""Model step: device time of the Pallas attention kernels (leaf
instructions under ``attn`` whose ``op_name`` holds a ``pallas_call``:
forward, dq, dk / dv), whatever the kind of layer: a model with no
``attn_full`` / ``attn_window`` sub-scope (OLMoE's) is read too, where
``_attn_scopes.kernel_step_ms`` sees none; every pass, a run of
``jit_train_step`` in the traced window, mean over the chips
(``_attn_parts``). None on a program without the seven part scopes, as
every metric of this helper."""

from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    return _attn_parts.step_ms(run, _attn_parts.KERNEL)
