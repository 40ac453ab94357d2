"""paddle_tpu.nlp — transformer language models for the BASELINE configs
(BERT-base pretraining = config 2, GPT-2 medium = config 3; the reference
ships these as test models dist_transformer.py / the nn.Transformer stack)."""
from .gpt import (GPTModel, GPTForPretraining, GPTConfig, gpt2_small,
                  gpt2_medium, gpt_generate, generate)
from .bert import BertModel, BertForPretraining, BertConfig, bert_base, bert_large
from .llama import (LlamaModel, LlamaForCausalLM, LlamaConfig,
                    llama_pretrain_loss)
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM
from .granite_hybrid import GraniteHybridConfig, GraniteHybridForCausalLM
from .deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM
