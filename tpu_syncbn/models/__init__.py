"""Model zoo: the architectures named by the reference's capability configs
(ResNet-18/50, RetinaNet-R50-FPN, DCGAN/SNGAN — BASELINE.json), plus the
transformer LM that exercises the long-context path, and the two
decoder LMs that train through ``DataParallel``: the looped one and the
latent-attention mixture of experts."""

from tpu_syncbn.models import detection, gan, looped_lm, moe_lm, transformer
from tpu_syncbn.models.looped_lm import LoopedDecoderLM
from tpu_syncbn.models.moe_lm import LatentMoEDecoderLM
from tpu_syncbn.models.transformer import init_transformer_lm, transformer_lm
from tpu_syncbn.models.gan import (
    DCGANGenerator,
    DCGANDiscriminator,
    SNGANDiscriminator,
    SNConv,
)
from tpu_syncbn.models.retinanet import RetinaNet, FPN, RetinaHead, retinanet_r50_fpn
from tpu_syncbn.models.resnet import (
    ResNet,
    BasicBlock,
    Bottleneck,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
    RESNETS,
)

__all__ = [
    "gan",
    "DCGANGenerator",
    "DCGANDiscriminator",
    "SNGANDiscriminator",
    "SNConv",
    "detection",
    "RetinaNet",
    "FPN",
    "RetinaHead",
    "retinanet_r50_fpn",
    "ResNet",
    "BasicBlock",
    "Bottleneck",
    "resnet18",
    "resnet34",
    "resnet50",
    "resnet101",
    "resnet152",
    "RESNETS",
    "transformer",
    "init_transformer_lm",
    "transformer_lm",
    "looped_lm",
    "LoopedDecoderLM",
    "moe_lm",
    "LatentMoEDecoderLM",
]
