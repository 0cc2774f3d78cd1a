"""Model zoo: functional JAX models (params pytree + pure apply).

Parity with reference scaletorch/models/__init__.py:1-9 — Llama, Qwen3,
Qwen3-MoE, GPT(MoE), LeNet, plus the standalone attention-variant library
(MHA/MQA/GQA/MLA) and the attention backend registry.
"""

from scaletorch_tpu.models.registry import (  # noqa: F401
    get_attention_backend,
    register_attention_backend,
    resolve_attention_backend,
)
from scaletorch_tpu.models.llama import Llama, LlamaConfig  # noqa: F401
from scaletorch_tpu.models.qwen3 import Qwen3, Qwen3Config  # noqa: F401
from scaletorch_tpu.models.qwen3_moe import Qwen3MoE, Qwen3MoEConfig  # noqa: F401
from scaletorch_tpu.models.olmoe import Olmoe, OlmoeConfig  # noqa: F401
from scaletorch_tpu.models.olmo_hybrid import (  # noqa: F401
    OlmoHybrid,
    OlmoHybridConfig,
)
from scaletorch_tpu.models.qwen3_next import (  # noqa: F401
    Qwen3Next,
    Qwen3NextConfig,
)
from scaletorch_tpu.models.afmoe import Afmoe, AfmoeConfig  # noqa: F401
from scaletorch_tpu.models.jamba import Jamba, JambaConfig  # noqa: F401
from scaletorch_tpu.models.pangu_ultra_moe import (  # noqa: F401
    PanguUltraMoE,
    PanguUltraMoEConfig,
)
from scaletorch_tpu.models.kimi_linear import (  # noqa: F401
    KimiLinear,
    KimiLinearConfig,
)
from scaletorch_tpu.models.mimo_v2_flash import (  # noqa: F401
    MimoV2Flash,
    MimoV2FlashConfig,
)
from scaletorch_tpu.models.granite_moe_hybrid import (  # noqa: F401
    GraniteMoeHybrid,
    GraniteMoeHybridConfig,
)
from scaletorch_tpu.models.gpt_moe import GPTMoE, GPTMoEConfig  # noqa: F401
from scaletorch_tpu.models.lenet import LeNet, LeNetConfig  # noqa: F401
from scaletorch_tpu.models.resnet import ResNetConfig  # noqa: F401

# Register the non-default attention backends (flash; ring arrives with the
# context-parallel module).
import scaletorch_tpu.ops  # noqa: E402,F401
