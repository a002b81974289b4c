"""Model zoo for benchmarks and examples.

The reference ships models only as examples/benchmark harnesses
(``examples/tensorflow2_synthetic_benchmark.py`` uses Keras ResNet-50,
``examples/tensorflow2_mnist.py`` a small CNN); these are their TPU-native
(flax) equivalents, used by ``benchmarks/``, the examples and the test
suite.
"""

from horovod_tpu.models.resnet import (  # noqa: F401
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from horovod_tpu.models.vgg import VGG, VGG16, VGG19  # noqa: F401
from horovod_tpu.models.inception import InceptionV3  # noqa: F401
from horovod_tpu.models.mnist import MnistCNN  # noqa: F401
from horovod_tpu.models.mlp import MLP  # noqa: F401
from horovod_tpu.models.transformer import (  # noqa: F401
    Experts,
    GatedDelta,
    Layer,
    Mamba2,
    SwiGLU,
    TransformerLM,
    TransformerTiny,
    TransformerSmall,
    Yarn,
    generate,
    transformer_param_specs,
)
