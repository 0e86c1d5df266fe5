"""Model zoo — symbol builders for the reference's target workloads
(BASELINE.json configs): MLP/LeNet (MNIST), ResNet-50 (ImageNet DP),
VGG-16 (SSD backbone), Inception-BN, DCGAN generator/discriminator, the
bucketed LSTM language model and the OLMoE, AFMoE (Trinity), Qwen3-Next,
DeepSeek-V3 (latent attention), ZAYA1 (compressed convolutional attention,
an MLP router), Kimi Linear (a delta rule gated a key channel, latent
attention without positions) and Keye-VL-2.0 (the text decoder: attention over
the keys a learned indexer selects) sparse-expert decoders, Ouro (a
looped dense decoder: one stack run four times over the same weights, an
exit after every pass), SDAR (a sparse-expert decoder trained as a
block-diffusion model: every row read twice, a noised copy and a clean one)
, Mellum 2 (a sparse-expert decoder whose window and full layers each turn
by their own rotary schedule, the full ones by YaRN's) and Phi-4-mini-flash
(SambaY: Mamba-1 selective scans and differential attention over a band,
then a cross-decoder whose layers read one layer's keys and values and one
scan's memory).

Reference: ``example/image-classification/symbols/*.py`` and
``example/rnn``/``example/gan``. Builders return plain Symbols usable with
mx.mod.Module.
"""

from .mlp import get_symbol as mlp
from .lenet import get_symbol as lenet
from .resnet import get_symbol as resnet
from .vgg import get_symbol as vgg
from .inception_bn import get_symbol as inception_bn
from .alexnet import get_symbol as alexnet
from .googlenet import get_symbol as googlenet
from .inception_v3 import get_symbol as inception_v3
from .resnext import get_symbol as resnext
from .inception_resnet_v2 import get_symbol as inception_resnet_v2
from .dcgan import make_generator as dcgan_generator
from .dcgan import make_discriminator as dcgan_discriminator
from .lstm_lm import lstm_lm_serving_sym_gen, lstm_lm_sym_gen
from .olmoe import olmoe_sym_gen
from .afmoe import afmoe_sym_gen
from .qwen3_next import qwen3_next_sym_gen
from .deepseek_v3 import deepseek_v3_sym_gen
from .zaya import zaya_sym_gen
from .kimi_linear import kimi_linear_sym_gen
from .keye_vl2 import keye_vl2_sym_gen
from .ouro import ouro_sym_gen
from .sdar import sdar_sym_gen
from .mellum import mellum_sym_gen
from .phi4flash import phi4flash_sym_gen
from . import ssd
from . import zoo
from .zoo import SCORE_SYMBOLS
