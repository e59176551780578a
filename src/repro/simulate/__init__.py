"""TPU-scale simulation tier: hardware book + calibrated step-time model."""
from repro.simulate.hardware import (  # noqa: F401
    HW_BY_DEVICE_KIND, HW_BY_NAME, HardwareGen, V5E, V5P, V6E,
    hw_for_device_kind)
from repro.simulate.step_time import StepTimeModel  # noqa: F401
