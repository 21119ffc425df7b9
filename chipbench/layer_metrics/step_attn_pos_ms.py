"""Model step: device time of the leaf instructions under the scope
``attn_pos`` (QK-norm and RoPE on q and k; latent attention: RoPE on the
two rotary parts, nested inside ``mla_latent``, whose own metric keeps
counting them; every pass), a run of ``jit_train_step`` in the traced
window, mean over the chips (``_attn_parts``). A fusion has one name:
RoPE that XLA folds into a projection's epilogue reads ``attn_qkv``."""

from chipbench.layer_metrics import _attn_parts


def read(run: dict):
    return _attn_parts.step_ms(run, _attn_parts.POS)
