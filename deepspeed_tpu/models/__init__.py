from . import (afmoe, bailing_hybrid, bert, bloom, deepseek_v2, falcon, glm_moe_dsa, gpt2, gptj, granite_moe_hybrid, lfm2, llama,
               longcat_flash, mistral, mixtral, nemotron_h, olmoe, opt, phi, qwen, transformer)
from .afmoe import AfmoeConfig
from .bailing_hybrid import BailingHybridConfig
from .bert import BertConfig
from .bloom import BloomConfig
from .deepseek_v2 import DeepseekV2Config
from .falcon import FalconConfig
from .glm_moe_dsa import GlmMoeDsaConfig
from .gpt2 import GPT2Config
from .gptj import GPTJConfig
from .granite_moe_hybrid import GraniteMoeHybridConfig
from .lfm2 import Lfm2Config
from .llama import LlamaConfig
from .longcat_flash import LongcatFlashConfig
from .mistral import MistralConfig
from .mixtral import MixtralConfig
from .nemotron_h import NemotronHConfig
from .olmoe import OlmoeConfig
from .opt import OPTConfig
from .phi import PhiConfig
from .qwen import QwenConfig
