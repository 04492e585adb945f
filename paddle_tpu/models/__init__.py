"""Flagship model families (parity targets from BASELINE.json configs)."""
from . import (ernie, gpt, llama, longcat_flash, nemotron_h, ouro,  # noqa: F401
               unet)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieForPretraining, ErnieForSequenceClassification,
    ErnieModel,
)
from .gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM, LlamaModel  # noqa: F401
from .longcat_flash import (  # noqa: F401
    LongcatFlashConfig, LongcatFlashForCausalLM, LongcatFlashModel,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig, NemotronHForCausalLM, NemotronHModel,
)
from .ouro import OuroConfig, OuroForCausalLM, OuroModel  # noqa: F401
from .unet import UNet2DConditionModel, UNetConfig  # noqa: F401
