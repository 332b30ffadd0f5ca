"""Model factories for the BASELINE exercise configs (SURVEY §6):
  1. LeNet/MLP on MNIST (Module API)        -> lenet.get_lenet / get_mlp
  2. ResNet-50 ImageNet (Gluon hybridize)   -> gluon.model_zoo resnet50_v1
  3. LSTM word language model               -> word_lm.RNNModel
  4. SSD object detection (multibox ops)    -> ssd.SSDLite
  5. Sparse linear classification           -> sparse_linear.SparseLinear
"""
from .lenet import get_lenet, get_mlp, get_resnetish, LeNet
from .word_lm import RNNModel
from .ssd import SSDLite
from .sparse_linear import SparseLinear
from .fm import FactorizationMachine

# mesh-first transformer LM (capability upgrade: dp/tp/sp/ep parallelism)
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_apply, transformer_shardings,
                          make_train_step as make_transformer_train_step,
                          lm_loss)

# latent attention + dropless sparse experts (the second served LM family)
from .latent_moe import (LatentMoEConfig, init_latent_moe_params,
                         latent_moe_apply)

# window and full attention, grouped-query and gated, over the same expert
# layer (the third served LM family)
from .afmoe import AfmoeConfig, init_afmoe_params, afmoe_apply

# attention heads and state-space heads side by side in every layer (the
# fourth served LM family)
from .falcon_h1 import (FalconH1Config, init_falcon_h1_params,
                        falcon_h1_apply)

# layers that are each one mixer by a pattern's letter: a state-space
# mixer, a routed squared-ReLU expert layer or a grouped-query attention
# (the fifth served LM family)
from .nemotron_h import (NemotronHConfig, init_nemotron_h_params,
                         nemotron_h_apply)
