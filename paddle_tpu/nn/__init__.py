"""paddle.nn equivalent (reference ``python/paddle/nn/__init__.py``)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from . import utils  # noqa: F401
from .layer.layers import Layer, ParamAttr  # noqa: F401
from .layer.container import LayerDict, LayerList, ParameterList, Sequential  # noqa: F401
from .layer.common import *  # noqa: F401,F403
from .layer.conv import *  # noqa: F401,F403
from .layer.norm import *  # noqa: F401,F403
from .layer.activation import *  # noqa: F401,F403
from .layer.pooling import *  # noqa: F401,F403
from .layer.loss import *  # noqa: F401,F403
from .decode import BeamSearchDecoder, Decoder, dynamic_decode  # noqa: F401
from .layer.transformer import *  # noqa: F401,F403
from .layer.rnn import *  # noqa: F401,F403
from .layer.experts import DroplessExperts  # noqa: F401
from .layer.kda import KimiDeltaAttention  # noqa: F401
from .layer.mamba import Mamba2Mixer  # noqa: F401
from . import quant  # noqa: F401

from ..utils.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401
