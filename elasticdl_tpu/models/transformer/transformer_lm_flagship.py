"""The flagship LM at its published widths, as a model-def module.

The model contract takes no parameters (`ModelSpec.build_model()` calls
`custom_model()` bare), so `edl train --model_def
elasticdl_tpu.models.transformer.transformer_lm_flagship` is how the
`flagship_config()` widths (vocab 32768, d_model 1024, 8 heads x 128,
12 layers, S=4096) go through the normal entry point.
"""

from elasticdl_tpu.models.transformer.transformer_lm import (  # noqa: F401
    custom_model as _custom_model,
    feed,
    flagship_config,
    loss,
    optimizer,
    param_specs,
)


def custom_model():
    return _custom_model(flagship_config())
