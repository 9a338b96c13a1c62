"""Model zoo registry.

Parity target: reference dl_trainer.py:87-135 `create_net`, which dispatches
22 model names to local modules or torchvision. Here every architecture is a
Flax module built in-repo (SURVEY.md §2.7 inventory). `create_model` returns
the module plus a `ModelMeta` describing the canonical input so callers
(trainer, tests, bench) can build example batches without per-model switches.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax.numpy as jnp

# Dataset -> (num_classes, example input HWC / sequence spec)
DATASET_CLASSES = {
    "mnist": 10,
    "cifar10": 10,
    "imagenet": 1000,
    "ptb": 10000,
    "an4": 29,  # CTC label alphabet, reference labels.json (29 chars)
    "tokens": 98304,  # data/tokens.py default; --vocab-size names a share
}


@dataclasses.dataclass(frozen=True)
class ModelMeta:
    name: str
    dataset: str
    num_classes: int
    # example input shape WITHOUT batch dim; image models: (H, W, C) NHWC;
    # lm models: (seq_len,) int tokens; ctc audio: (time, freq)
    input_shape: tuple[int, ...]
    input_dtype: Any = jnp.float32
    task: str = "classify"  # classify | lm | ctc
    has_aux_logits: bool = False  # googlenet/inceptionv3 style aux heads
    has_carry: bool = False  # recurrent models with BPTT carry state
    # lm models whose `model(x, targets=y)` returns (per-token loss (B, T),
    # statistics for the step's metrics) and never the whole batch's logits
    fused_loss: bool = False


_REGISTRY: dict[str, Callable[[int], tuple[Any, ModelMeta]]] = {}
_TAKES_SHARE: set[str] = set()  # factories with layers_held / experts_held


def register(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def model_names() -> list[str]:
    return sorted(_REGISTRY)


# canonical image input per dataset (used to keep meta.input_shape consistent
# under dataset overrides)
DATASET_INPUT_HWC = {
    "mnist": (28, 28, 1),
    "cifar10": (32, 32, 3),
    "imagenet": (224, 224, 3),
}


def create_model(
    name: str, dataset: Optional[str] = None,
    num_classes: Optional[int] = None, **share,
):
    """Build (module, meta) for a model name (reference create_net,
    dl_trainer.py:87-135). dataset/num_classes override the model's default;
    for image models a dataset override also retargets meta.input_shape so
    callers building batches from meta stay consistent. `share` (the part of
    the model one chip holds: `layers_held`, `experts_held`, `tensor_share`)
    goes to the
    factories that take it (the mellum2 family); for any other model it is
    an error."""
    if name not in _REGISTRY:
        raise ValueError(f"unknown model {name!r}; known: {model_names()}")
    share = {k: v for k, v in share.items() if v is not None}
    if share and name not in _TAKES_SHARE:
        raise ValueError(
            f"model {name!r} cannot be held in part ({sorted(share)}); "
            f"only {sorted(_TAKES_SHARE)} can")
    factory = functools.partial(_REGISTRY[name], **share)
    module, meta = factory(num_classes)
    if dataset is not None and dataset != meta.dataset:
        nc = num_classes or DATASET_CLASSES.get(dataset, meta.num_classes)
        if nc != meta.num_classes:
            module, meta = factory(nc)
        updates: dict = {"dataset": dataset}
        if meta.task == "classify" and dataset in DATASET_INPUT_HWC:
            updates["input_shape"] = DATASET_INPUT_HWC[dataset]
        meta = dataclasses.replace(meta, **updates)
    return module, meta


def _image_meta(name, dataset, nc, hwc, **kw) -> ModelMeta:
    return ModelMeta(name=name, dataset=dataset, num_classes=nc, input_shape=hwc, **kw)


MNIST_HWC = (28, 28, 1)
CIFAR_HWC = (32, 32, 3)
IMAGENET_HWC = (224, 224, 3)


@register("mnistnet")
def _mnistnet(nc):
    from mgwfbp_tpu.models.simple import MnistNet

    nc = nc or 10
    return MnistNet(nc), _image_meta("mnistnet", "mnist", nc, MNIST_HWC)


@register("lenet")
def _lenet(nc):
    from mgwfbp_tpu.models.simple import LeNet

    nc = nc or 10
    return LeNet(nc), _image_meta("lenet", "mnist", nc, MNIST_HWC)


@register("fcn5net")
def _fcn5(nc):
    from mgwfbp_tpu.models.simple import FCN5Net

    nc = nc or 10
    return FCN5Net(nc), _image_meta("fcn5net", "mnist", nc, MNIST_HWC)


@register("lr")
def _linreg(nc):
    from mgwfbp_tpu.models.simple import LinearRegression

    nc = nc or 10
    return LinearRegression(nc), _image_meta("lr", "mnist", nc, MNIST_HWC)


@register("caffe_cifar")
def _caffe_cifar(nc):
    from mgwfbp_tpu.models.simple import CaffeCifar

    nc = nc or 10
    return CaffeCifar(nc), _image_meta("caffe_cifar", "cifar10", nc, CIFAR_HWC)


def _register_cifar_resnet(depth: int):
    @register(f"resnet{depth}")
    def _factory(nc, depth=depth):
        from mgwfbp_tpu.models.resnet_cifar import CifarResNet

        nc = nc or 10
        return (
            CifarResNet(depth=depth, num_classes=nc),
            _image_meta(f"resnet{depth}", "cifar10", nc, CIFAR_HWC),
        )


for _d in (20, 32, 44, 56, 110):
    _register_cifar_resnet(_d)


@register("preresnet110")
def _preresnet110(nc):
    from mgwfbp_tpu.models.resnet_cifar import preresnet110

    nc = nc or 10
    return preresnet110(nc), _image_meta("preresnet110", "cifar10", nc, CIFAR_HWC)


@register("preresnet20")
def _preresnet20(nc):
    from mgwfbp_tpu.models.resnet_cifar import preresnet20

    nc = nc or 10
    return preresnet20(nc), _image_meta("preresnet20", "cifar10", nc, CIFAR_HWC)


def _register_imagenet_resnet(depth: int):
    @register(f"resnet{depth}")
    def _factory(nc, depth=depth):
        from mgwfbp_tpu.models.resnet_imagenet import imagenet_resnet

        nc = nc or 1000
        return (
            imagenet_resnet(depth, nc),
            _image_meta(f"resnet{depth}", "imagenet", nc, IMAGENET_HWC),
        )


for _d in (18, 34, 50, 101, 152):
    _register_imagenet_resnet(_d)


def _register_vgg_cifar(depth: int):
    @register(f"vgg{depth}")
    def _factory(nc, depth=depth):
        from mgwfbp_tpu.models.vgg import VGGCifar

        nc = nc or 10
        return (
            VGGCifar(cfg=f"vgg{depth}", num_classes=nc),
            _image_meta(f"vgg{depth}", "cifar10", nc, CIFAR_HWC),
        )


for _d in (11, 13, 16, 19):
    _register_vgg_cifar(_d)


@register("vgg16i")
def _vgg16i(nc):
    from mgwfbp_tpu.models.vgg import VGGImageNet

    nc = nc or 1000
    return (
        VGGImageNet(cfg="vgg16", num_classes=nc),
        _image_meta("vgg16i", "imagenet", nc, IMAGENET_HWC),
    )


@register("alexnet")
def _alexnet(nc):
    from mgwfbp_tpu.models.alexnet import AlexNet

    nc = nc or 1000
    return AlexNet(nc), _image_meta("alexnet", "imagenet", nc, IMAGENET_HWC)


@register("resnext29")
def _resnext29(nc):
    from mgwfbp_tpu.models.resnext import ResNeXt29

    nc = nc or 10
    return ResNeXt29(num_classes=nc), _image_meta("resnext29", "cifar10", nc, CIFAR_HWC)


@register("densenet")
def _densenet_bc(nc):
    from mgwfbp_tpu.models.densenet import densenet_bc_100_12

    nc = nc or 10
    return densenet_bc_100_12(nc), _image_meta("densenet", "cifar10", nc, CIFAR_HWC)


def _register_imagenet_densenet(depth: int):
    @register(f"densenet{depth}")
    def _factory(nc, depth=depth):
        from mgwfbp_tpu.models.densenet import imagenet_densenet

        nc = nc or 1000
        return (
            imagenet_densenet(depth, nc),
            _image_meta(f"densenet{depth}", "imagenet", nc, IMAGENET_HWC),
        )


for _d in (121, 161, 201):
    _register_imagenet_densenet(_d)


@register("googlenet")
def _googlenet(nc):
    from mgwfbp_tpu.models.googlenet import GoogLeNet

    nc = nc or 1000
    return (
        GoogLeNet(num_classes=nc),
        _image_meta("googlenet", "imagenet", nc, IMAGENET_HWC, has_aux_logits=True),
    )


@register("inceptionv3")
def _inceptionv3(nc):
    from mgwfbp_tpu.models.inception import InceptionV3

    nc = nc or 1000
    return (
        InceptionV3(num_classes=nc),
        _image_meta("inceptionv3", "imagenet", nc, (299, 299, 3), has_aux_logits=True),
    )


@register("inceptionv4")
def _inceptionv4(nc):
    from mgwfbp_tpu.models.inception import InceptionV4

    nc = nc or 1000
    return (
        InceptionV4(num_classes=nc),
        _image_meta("inceptionv4", "imagenet", nc, (299, 299, 3)),
    )


@register("lstm")
def _lstm(nc):
    from mgwfbp_tpu.models.lstm import PTBLSTM

    nc = nc or DATASET_CLASSES["ptb"]
    return (
        PTBLSTM(vocab_size=nc),
        ModelMeta(
            name="lstm", dataset="ptb", num_classes=nc, input_shape=(35,),
            input_dtype=jnp.int32, task="lm", has_carry=True,
        ),
    )


@register("transformer")
def _transformer(nc):
    from mgwfbp_tpu.models.transformer import TransformerLM

    nc = nc or DATASET_CLASSES["ptb"]
    return (
        TransformerLM(vocab_size=nc),
        ModelMeta(
            name="transformer", dataset="ptb", num_classes=nc,
            input_shape=(35,), input_dtype=jnp.int32, task="lm",
            has_carry=False,
        ),
    )


@register("lstman4")
def _lstman4(nc):
    from mgwfbp_tpu.models.deepspeech import DeepSpeech

    nc = nc or DATASET_CLASSES["an4"]
    return (
        DeepSpeech(num_classes=nc),
        ModelMeta(
            name="lstman4", dataset="an4", num_classes=nc,
            input_shape=(201, 161), task="ctc",  # (time, freq=161)
        ),
    )


def _held_lm_meta(name: str, nc: int, window_len: int) -> ModelMeta:
    """A decoder LM over the `tokens` dataset that takes its loss itself and
    can be held in part (the mellum2, granite4h, laguna_xs2, phi4flash,
    qwen3next, xing4 and nemotron3s families)."""
    return ModelMeta(
        name=name, dataset="tokens", num_classes=nc,
        input_shape=(window_len,), input_dtype=jnp.int32, task="lm",
        has_carry=False, fused_loss=True,
    )


def parse_layers_held(value) -> Optional[tuple[int, int]]:
    """`--layers-held` as (first, count): None, a bare N (the first N, 0:N)
    or FIRST:COUNT, a pipeline stage anywhere in the model (the form
    `--experts-held` has)."""
    if value is None or isinstance(value, tuple):
        return value
    try:
        first, _, count = str(value).rpartition(":")
        return int(first or 0), int(count)
    except ValueError:
        raise ValueError(
            f"--layers-held {value!r} is neither N nor FIRST:COUNT (integers)"
        ) from None


def parse_tensor_share(value) -> Optional[tuple[int, int]]:
    """`--tensor-share` as (index, of): None, or INDEX:OF, member INDEX of
    the OF chips that share each layer's heads."""
    if value is None or isinstance(value, tuple):
        return value
    try:
        index, of = (int(v) for v in str(value).split(":"))
    except ValueError:
        raise ValueError(
            f"--tensor-share {value!r} is not INDEX:OF (two integers)"
        ) from None
    return index, of


def _register_held_lm(name: str, load: Callable[[], tuple[Any, Any]],
                      window_len: int, takes_experts: bool,
                      takes_first: bool = False, takes_tensor: bool = False):
    """A decoder held in part by layers and vocabulary and, with
    `takes_experts`, by routed experts, and, with `takes_tensor`, by the
    heads of each layer. `load` imports the family's module when the model
    is first built and returns (class, shape). With `takes_first` the module
    takes its layers as (first, count); the others hold their first N and
    are handed N."""
    @register(name)
    def _factory(nc, layers_held=None, experts_held=None, tensor_share=None):
        cls, shape = load()
        nc = nc or shape.vocab_size
        layers = parse_layers_held(layers_held)
        if layers is not None and not takes_first:
            if layers[0] != 0:
                raise ValueError(
                    f"model {name!r} holds its first layers only: "
                    f"--layers-held {layers[0]}:{layers[1]} with a FIRST "
                    "other than 0 waits for a configuration that needs it")
            layers = layers[1]
        share = {"layers_held": layers}
        if takes_experts:
            share["experts_held"] = experts_held or (0, shape.num_experts)
        elif experts_held is not None:
            raise ValueError(
                f"model {name!r} is dense: it has no experts to hold in part")
        tensor = parse_tensor_share(tensor_share)
        if takes_tensor:
            share["tensor_share"] = tensor or (0, 1)
        elif tensor is not None:
            raise ValueError(
                f"model {name!r} holds every layer's heads whole: "
                "--tensor-share waits for a configuration that needs it")
        return (cls(vocab_size=nc, shape=shape, **share),
                _held_lm_meta(name, nc, window_len))

    _TAKES_SHARE.add(name)


def _mellum2(tiny: bool):
    from mgwfbp_tpu.models import mellum

    return mellum.Mellum2LM, mellum.MELLUM2_TINY if tiny else mellum.MELLUM2


def _laguna_xs2(tiny: bool):
    from mgwfbp_tpu.models import laguna

    return laguna.LagunaLM, (
        laguna.LAGUNA_XS2_TINY if tiny else laguna.LAGUNA_XS2)


def _granite4h(tiny: bool):
    from mgwfbp_tpu.models import granite

    return granite.Granite4HLM, (
        granite.GRANITE4H_TINY if tiny else granite.GRANITE4H)


def _phi4flash(tiny: bool):
    from mgwfbp_tpu.models import phi4flash

    return phi4flash.Phi4FlashLM, (
        phi4flash.PHI4FLASH_TINY if tiny else phi4flash.PHI4FLASH)


def _qwen3next(tiny: bool):
    from mgwfbp_tpu.models import qwen3next

    return qwen3next.Qwen3NextLM, (
        qwen3next.QWEN3NEXT_TINY if tiny else qwen3next.QWEN3NEXT)


def _xing4(tiny: bool):
    from mgwfbp_tpu.models import xing4

    return xing4.Xing4LM, xing4.XING4_TINY if tiny else xing4.XING4


def _nemotron3s(tiny: bool):
    from mgwfbp_tpu.models import nemotronh

    return nemotronh.NemotronHLM, (
        nemotronh.NEMOTRON3S_TINY if tiny else nemotronh.NEMOTRON3S)


# each family at its published widths, and at a size the CPU tests hold:
# mellum2 (hidden 64, 2 key/value heads, 8 experts top 2, window 16);
# laguna_xs2 (hidden 64, 6 / 8 query heads over 2 key heads of 16, 16 experts
# top 2 and a shared one, window 16, five layers: the dense one first);
# granite4h (hidden 32, 4 Mamba heads of 16, state 8, chunk 16, four layers);
# phi4flash (hidden 32, 4 / 2 heads of 8, window 16, state 4, eight layers:
# every kind by the publisher's rule), whose stage may start anywhere;
# qwen3next (hidden 32, 2 key / 4 value delta-rule heads of 8, 4 / 1 attention
# heads of 16, 16 experts top 3 and a gated shared one, eight layers);
# xing4 (hidden 32, four residual streams, 4 latent-attention heads scoring
# over 16 + 8 and summing values of 16, 8 experts top 2 chosen with a
# selection bias and a shared one, four layers: two dense first), whose stage
# may start anywhere;
# nemotron3s (hidden 32, seven layers of ONE mixer each, M E M * E M E: 8
# Mamba heads of 8 over 4 B/C groups, 8 / 2 attention heads of 8, 8 relu^2
# experts top 3 of width 24 in a latent of 16 and a shared one of 48), whose
# stage may start anywhere and whose heads may be shared out (--tensor-share)
for _name, _load, _experts, _first, _tensor in (
        ("mellum2", _mellum2, True, False, False),
        ("laguna_xs2", _laguna_xs2, True, False, False),
        ("granite4h", _granite4h, False, False, False),
        ("phi4flash", _phi4flash, False, True, False),
        ("qwen3next", _qwen3next, True, False, False),
        ("xing4", _xing4, True, True, False),
        ("nemotron3s", _nemotron3s, True, True, True)):
    _register_held_lm(
        _name, functools.partial(_load, False), 8192, _experts, _first,
        _tensor)
    _register_held_lm(
        _name + "_tiny", functools.partial(_load, True), 64, _experts, _first,
        _tensor)
