from .steps import (  # noqa: F401
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
