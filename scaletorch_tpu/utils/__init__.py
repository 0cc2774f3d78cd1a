"""Cross-cutting utilities: device info, MFU math, logging, monitoring."""

from scaletorch_tpu.utils.device import (  # noqa: F401
    get_device_kind,
    get_theoretical_flops,
    device_memory_stats,
)
from scaletorch_tpu.utils.misc import (  # noqa: F401
    get_mfu,
    get_flops_per_token,
    get_num_params,
    set_all_seed,
    to_readable_format,
)
from scaletorch_tpu.utils.env_info import (  # noqa: F401
    get_system_info,
    log_system_info,
)
