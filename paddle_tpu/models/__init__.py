"""Model zoo: LLM families the reference's distributed stack targets
(PaddleNLP llama/gpt/bert + MoE configs). Vision models live in
paddle_tpu.vision.models."""

from . import bert, gpt, jamba, llama, nemotron_h, pangu_moe, qwen2_moe  # noqa: F401
from .bert import BertConfig, BertForPreTraining, BertModel  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM  # noqa: F401
from .jamba import JambaConfig, JambaForCausalLM, jamba_tiny  # noqa: F401
from .llama import LlamaConfig, LlamaForCausalLM, llama_3_8b, llama_tiny  # noqa: F401
from .llama_pipe import LlamaForCausalLMPipe  # noqa: F401
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM, nemotron_h_tiny  # noqa: F401
from .pangu_moe import PanguMoEConfig, PanguMoEForCausalLM, pangu_moe_tiny  # noqa: F401
from .qwen2_moe import Qwen2MoeConfig, Qwen2MoeForCausalLM, qwen2_moe_tiny  # noqa: F401
