"""Model step: device time of the Pallas kernels under ``attn_linear`` in a
model whose linear layers are Gated DeltaNet: the delta rule's two
kernels with ONE decay a head and a key head's block read by both of its
value heads (``ray_tpu/ops/linear_attention.py`` ``_head_matrices``), and
the three convolution chains'; every pass, all such layers, a run of
``jit_train_step`` in the traced window, mean over the chips. None on a
program whose delta rule is plain XLA there (the scan: no such leaf).
It IS ``step_kda_kernel_ms``'s reading, under a name of this cell's own:
the accepted ``tests/chipbench/test_chipbench_kda_kernel.py`` holds that
entry's list to the one cell it was added for (PERF.md section 7)."""

from chipbench.layer_metrics import step_kda_kernel_ms


def read(run: dict):
    return step_kda_kernel_ms.read(run)
