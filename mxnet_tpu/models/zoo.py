"""Canonical model-zoo registry for the inference score sweep.

The symbol list swept by ``examples/benchmark_score.py`` — the reference's
``example/image-classification/benchmark_score.py`` sweeps the same span
(alexnet → inception-resnet-v2 / resnet-200).
"""

# The 14 zoo symbols of the published perf table, in sweep order.
SCORE_SYMBOLS = (
    "alexnet",
    "vgg-16",
    "googlenet",
    "inception-bn",
    "inception-v3",
    "inception-resnet-v2",
    "resnet-18",
    "resnet-34",
    "resnet-50",
    "resnet-101",
    "resnet-152",
    "resnet-200",
    "resnext-50",
    "resnext-101",
)


def get_symbol(network, num_classes=1000, **kwargs):
    """Build a zoo symbol by sweep name (``resnet-50``, ``inception-v3``...).

    Accepts every name in :data:`SCORE_SYMBOLS` plus the small-net builders
    (``mlp``, ``lenet``) and the bare aliases the example historically took
    (``vgg`` == ``vgg-16``).  ``dtype=...`` in ``kwargs`` reaches the
    builders that carry a low-precision recipe and is ignored by the rest.
    """
    from . import (alexnet, googlenet, inception_bn, inception_resnet_v2,
                   inception_v3, lenet, mlp, resnet, resnext, vgg)

    if network.startswith("resnet-"):
        return resnet(num_classes=num_classes,
                      num_layers=int(network.split("-")[1]), **kwargs)
    if network.startswith("resnext-"):
        return resnext(num_classes=num_classes,
                       num_layers=int(network.split("-")[1]), **kwargs)
    if network.startswith("vgg-"):
        return vgg(num_classes=num_classes,
                   num_layers=int(network.split("-")[1]), **kwargs)
    factories = {
        "vgg": vgg,
        "inception-bn": inception_bn,
        "inception-v3": inception_v3,
        "inception-resnet-v2": inception_resnet_v2,
        "googlenet": googlenet,
        "alexnet": alexnet,
        "lenet": lambda num_classes, **kw: lenet(**kw),
        "mlp": lambda num_classes, **kw: mlp(**kw),
    }
    if network in factories:
        return factories[network](num_classes=num_classes, **kwargs)
    raise ValueError(f"unknown network {network!r} "
                     f"(zoo sweep: {', '.join(SCORE_SYMBOLS)})")
