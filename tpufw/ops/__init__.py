from tpufw.ops.attention import multi_head_attention, xla_attention  # noqa: F401
from tpufw.ops.loss import chunked_cross_entropy  # noqa: F401
from tpufw.ops.norms import layer_norm, rms_norm  # noqa: F401
