from .mlp import MLP, LeNet
from .resnet import ResNet, resnet18, resnet34
from .bert import (BertConfig, BertModel, BertForPreTraining,
                   BertForSequenceClassification)
from .gpt import GPTConfig, GPTModel, GPTLMHeadModel, GPT_CONFIGS
from .ctr import WDL, DeepFM, DCN, DLRM
from .gnn import (DistGCN15D, GCNLayerOp, distgcn_15d_op, gcn_conv_op,
                  normalized_adjacency)
from .llama import (LlamaConfig, LlamaModel, LlamaForCausalLM,
                    BaichuanForCausalLM, LLAMA_CONFIGS)
from .qwen3_next import (Qwen3NextConfig, Qwen3NextModel,
                         Qwen3NextForCausalLM, QWEN3_NEXT_CONFIGS)
from .nemotron_h import (NemotronHConfig, NemotronHModel,
                         NemotronHForCausalLM, NEMOTRON_H_CONFIGS)
from .granite_hybrid import (GraniteHybridConfig, GraniteHybridModel,
                             GraniteHybridForCausalLM,
                             GRANITE_HYBRID_CONFIGS)
from .ling3 import (Ling3Config, Ling3Model, Ling3ForCausalLM,
                    LING3_CONFIGS)
from .ouro import (OuroConfig, OuroModel, OuroForCausalLM, OURO_CONFIGS,
                   record_exit_shares)
from .laguna import (LagunaConfig, LagunaModel, LagunaForCausalLM,
                     LAGUNA_CONFIGS)
from .xing4 import (Xing4Config, Xing4Model, Xing4ForCausalLM,
                    XING4_CONFIGS)
from .zaya1 import (Zaya1Config, Zaya1Model, Zaya1ForCausalLM,
                    ZAYA1_CONFIGS)
from .sdar import (SdarMoeConfig, SdarMoeModel, SdarMoeForCausalLM,
                   SDAR_CONFIGS)
from .phi4flash import (Phi4FlashConfig, Phi4FlashDecoderLayer,
                        Phi4FlashModel, Phi4FlashForCausalLM,
                        PHI4FLASH_CONFIGS)
from .mellum import MellumConfig, MellumForCausalLM, MELLUM_CONFIGS
from .evabyte import (EvaByteConfig, EvaByteDecoderLayer, EvaByteModel,
                      EvaByteForCausalLM, EVABYTE_CONFIGS)
from .llama_decode import build_greedy_decode, greedy_generate
from .hf_import import (load_hf_bert_weights, load_hf_gpt2_weights,
                        load_hf_llama_weights, export_hf_llama_weights,
                        load_hf_mixtral_weights,
                        load_hf_granite_hybrid_weights,
                        load_hf_ling3_weights)
from .zoo import (LogReg, CNN3, AlexNet, VGG, vgg16, vgg19,
                  RNNClassifier, LSTMClassifier)
from .rec import (RatingModelHead, MFHead, GMFHead, MLPHead, NeuMFHead,
                  NCFModel, REC_HEADS)
from .transformer import (TransformerConfig, Seq2SeqTransformer,
                          sinusoidal_positions)
from .transformer_decode import build_seq2seq_decode, seq2seq_generate
