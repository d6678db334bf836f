"""MobileNetV3-Small — the paper's own lightweight CNN (paper §IV-B).

Inverted residual blocks with depthwise convs, batch norm, squeeze-and-excite
and hard-swish, as torchvision's ``mobilenet_v3_small`` builds them
(``models/cnn.py``): 2,542,856 parameters at 224 px and 1,000 classes,
1,528,106 in 142 leaves at this config's 32 px and 10 classes.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="mobilenet-v3-small",
    family="cnn",
    source="MobileNetV3 [Howard et al. 2019, arXiv:1905.02244, Table 2]; paper §IV-B",
    cnn_variant="mobilenet_v3_small",
    image_size=32,
    image_channels=3,
    num_classes=10,
)
