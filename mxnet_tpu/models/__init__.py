"""Model zoo — symbol builders for the reference's acceptance workloads
(example/image-classification/symbols/, example/rnn/).

``get_symbol(name, num_classes, **kwargs)`` dispatches by name like the
reference's fit.py does (example/image-classification/common/fit.py).
"""
from . import lenet, mlp, alexnet, vgg, resnet, inception_bn, mobilenet
from . import googlenet, inception_v3, resnext
from . import lstm_lm
from . import qwen3_next
from . import kimi_linear
from . import zaya
from .zaya import zaya_sym
from . import trinity
from .trinity import trinity_sym

_BUILDERS = {
    "lenet": lenet.get_symbol,
    "mlp": mlp.get_symbol,
    "alexnet": alexnet.get_symbol,
    "vgg": vgg.get_symbol,
    "vgg16": lambda num_classes=1000, **kw: vgg.get_symbol(num_classes, 16, **kw),
    "vgg19": lambda num_classes=1000, **kw: vgg.get_symbol(num_classes, 19, **kw),
    "resnet": resnet.get_symbol,
    "resnet-18": lambda num_classes=1000, **kw: resnet.get_symbol(num_classes, 18, **kw),
    "resnet-34": lambda num_classes=1000, **kw: resnet.get_symbol(num_classes, 34, **kw),
    "resnet-50": lambda num_classes=1000, **kw: resnet.get_symbol(num_classes, 50, **kw),
    "resnet-101": lambda num_classes=1000, **kw: resnet.get_symbol(num_classes, 101, **kw),
    "resnet-152": lambda num_classes=1000, **kw: resnet.get_symbol(num_classes, 152, **kw),
    "inception-bn": inception_bn.get_symbol,
    "mobilenet": mobilenet.get_symbol,
    "googlenet": googlenet.get_symbol,
    "inception-v3": inception_v3.get_symbol,
    "resnext": resnext.get_symbol,
    "resnext-50": lambda num_classes=1000, **kw: resnext.get_symbol(
        num_classes, 50, **kw),
    "resnext-101": lambda num_classes=1000, **kw: resnext.get_symbol(
        num_classes, 101, **kw),
}


def get_symbol(name, num_classes=1000, **kwargs):
    key = name.lower()
    if key not in _BUILDERS:
        raise KeyError("unknown model %r; available: %s"
                       % (name, sorted(_BUILDERS)))
    return _BUILDERS[key](num_classes=num_classes, **kwargs)
