"""Model zoo covering the five BASELINE.md benchmark configs:

1. ResNet50/8   (reference test/test.py flagship)
2. VGG19/4      (deep sequential, large activations)
3. InceptionV3/6 (branching DAG)
4. MobileNetV2/2 (comm-bound)
5. BERT-Base/12 (one transformer block per stage)

Each family ships a ``*_tiny`` variant for fast CPU-mesh tests.
"""

from .brumby import brumby, brumby_tiny
from .cohere_moe import cohere_moe, cohere_moe_tiny
from .bert import BERT_BASE_12STAGE_CUTS, bert, bert_base, bert_tiny
from .gpt import gpt, gpt2_small, gpt_small, gpt_stage_cuts, gpt_tiny
from .moe import (moe_branched, moe_branched_tiny, moe_stage_cuts,
                  moe_tiny, moe_transformer)
from .olmoe import olmoe, olmoe_tiny
from .jamba import jamba, jamba_tiny
from .granite_hybrid import granite_hybrid, granite_hybrid_tiny
from .kimi_k2 import kimi_k2, kimi_k2_tiny
from .longcat_flash import longcat_flash, longcat_flash_tiny
from .lfm2_moe import lfm2_moe, lfm2_moe_tiny
from .mellum import mellum, mellum_tiny
from .nemotron_h import nemotron_h, nemotron_h_tiny
from .solar_open2 import solar_open2, solar_open2_tiny
from .inception import (INCEPTION_6STAGE_CUTS, inception, inception_tiny,
                        inception_v3)
from .mobilenet import (MOBILENETV2_2STAGE_CUTS, mobilenet_tiny, mobilenet_v2)
from .resnet import RESNET50_8STAGE_CUTS, resnet, resnet50, resnet_tiny
from .vgg import VGG19_4STAGE_CUTS, vgg, vgg19, vgg_tiny

__all__ = [
    "resnet", "resnet50", "resnet_tiny", "RESNET50_8STAGE_CUTS",
    "vgg", "vgg19", "vgg_tiny", "VGG19_4STAGE_CUTS",
    "inception", "inception_v3", "inception_tiny", "INCEPTION_6STAGE_CUTS",
    "mobilenet_v2", "mobilenet_tiny", "MOBILENETV2_2STAGE_CUTS",
    "bert", "bert_base", "bert_tiny", "BERT_BASE_12STAGE_CUTS",
    "gpt", "gpt2_small", "gpt_small", "gpt_tiny", "gpt_stage_cuts",
    "moe_transformer", "moe_tiny", "moe_stage_cuts",
    "moe_branched", "moe_branched_tiny",
    "olmoe", "olmoe_tiny",
    "brumby", "brumby_tiny",
    "cohere_moe", "cohere_moe_tiny",
    "jamba", "jamba_tiny",
    "granite_hybrid", "granite_hybrid_tiny",
    "kimi_k2", "kimi_k2_tiny",
    "longcat_flash", "longcat_flash_tiny",
    "mellum", "mellum_tiny",
    "lfm2_moe", "lfm2_moe_tiny",
    "nemotron_h", "nemotron_h_tiny",
    "solar_open2", "solar_open2_tiny",
]
