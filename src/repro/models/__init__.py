"""Config-driven model zoo (all ten assigned architectures)."""
from .model import (  # noqa: F401
    decode_step,
    RECURRENT_STATE,
    forward,
    init_decode_state,
    init_params,
    loss_fn,
    reset_recurrent_lanes,
)
from . import sharding  # noqa: F401
