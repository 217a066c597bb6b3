"""ops subpackage: attention dispatch, pallas flash attention, the one-pass
retention decode step, fp8 matmuls, weight-only quantization."""

from .fp8 import (
    DelayedScalingState,
    fp8_dot_general,
    fp8_dot_general_delayed,
    make_fp8_dot_general,
)
from .quantization import (
    Int4Config,
    Int8Config,
    QuantizationConfig,
    QuantizedDense,
    QuantizedTensor,
    dequantize,
    dequantize_params,
    is_quantized,
    quantize,
    quantize_model_params,
    quantize_params,
    quantized_matmul,
    quantized_nbytes,
)
from .retention import retention_step_onepass
