from repro.kernels.ssm_decode.ops import ssm_state_update  # noqa: F401
