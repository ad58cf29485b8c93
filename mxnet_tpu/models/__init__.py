"""Model zoo: symbol-graph builders for the reference's example models.

Reference analogue: ``example/image-classification/symbols/`` (resnet.py,
alexnet.py, vgg.py, lenet.py, mlp.py, …) — each file exposes
``get_symbol(num_classes, **kwargs)``. Here the builders default to NHWC
layout and channel-last BatchNorm, which is the layout the TPU's MXU/vector
units prefer; the reference's NCHW remains available via ``layout=``.
"""
from __future__ import annotations

from ..base import MXNetError
from . import (alexnet, decoder_lm, googlenet, inception_bn, inception_resnet_v2,  # noqa: F401
               inception_v3, inception_v4, lenet, mlp,
               mobilenet, resnet, resnext, transformer,
               transformer_sym, vgg)
from .transformer import TransformerConfig, TransformerLM  # noqa: F401

_MODELS = {
    "resnet": resnet.get_symbol,
    "alexnet": alexnet.get_symbol,
    "vgg": vgg.get_symbol,
    "lenet": lenet.get_symbol,
    "mlp": mlp.get_symbol,
    "googlenet": googlenet.get_symbol,
    "resnet-v1": lambda **kw: resnet.get_symbol(
        **{**kw, "version": 1}),
    "inception-bn": inception_bn.get_symbol,
    "inception-v3": inception_v3.get_symbol,
    "inception-v4": inception_v4.get_symbol,
    "inception-resnet-v2": inception_resnet_v2.get_symbol,
    "mobilenet": mobilenet.get_symbol,
    "resnext": resnext.get_symbol,
    "transformer_lm": transformer_sym.get_symbol,
    "decoder_lm": decoder_lm.get_symbol,
}


def get_symbol(network: str, **kwargs):
    """Build a model symbol by name (reference: train_imagenet.py
    ``importlib.import_module('symbols.' + args.network).get_symbol``)."""
    if network not in _MODELS:
        raise MXNetError(
            f"unknown network {network!r}; available: {sorted(_MODELS)}")
    return _MODELS[network](**kwargs)
