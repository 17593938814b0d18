from tpufw.utils.hardware import (  # noqa: F401
    ChipSpec,
    CHIP_SPECS,
    detect_chip,
)
