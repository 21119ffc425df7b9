"""Model step: device time of the leaf instructions under the sub-scope
``attn_linear`` (everything of the KDA layers' token mixers, ALL of them
together: the q / k / v projections, the short convolutions with SiLU and
the l2 norms, the gates, the chunked delta rule, the gated head norm, the
output projection and the residual add; every pass), a run of
``jit_train_step`` in the traced window, mean over the chips
(``_named_scope``). With ``step_attn_full_ms`` it adds up to
``step_attn_ms`` in a model whose other layers are ``attn_full``."""

from chipbench.layer_metrics import _named_scope


def read(run: dict):
    return _named_scope.step_ms(run, "attn_linear")
