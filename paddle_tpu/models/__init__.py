"""Model zoo — TPU-native builds of the reference benchmark models
(ref ``benchmark/fluid/models/``: mnist, resnet, vgg, stacked_dynamic_lstm,
machine_translation, se_resnext; plus the BASELINE.json configs: Transformer
-base NMT, BERT-base pretrain, DeepFM CTR).

Every model module exposes builder functions that construct a fluid-style
symbolic program in the current default program and return a
:class:`ModelSpec` with the loss var, feed list, and a synthetic-batch
sampler (so tests and the benchmark don't need real datasets)."""

from .common import ModelSpec, baseline  # noqa: F401
from . import mnist  # noqa: F401
from . import resnet  # noqa: F401
from . import vgg  # noqa: F401
from . import se_resnext  # noqa: F401
from . import stacked_lstm  # noqa: F401
from . import transformer  # noqa: F401
from . import qwen3_next  # noqa: F401
from . import nemotron_h  # noqa: F401
from . import bert  # noqa: F401
from . import deepfm  # noqa: F401
from . import word2vec  # noqa: F401
from . import ocr_ctc  # noqa: F401
from . import ssd  # noqa: F401
from . import label_semantic_roles  # noqa: F401
from . import books  # noqa: F401
from . import machine_translation  # noqa: F401
