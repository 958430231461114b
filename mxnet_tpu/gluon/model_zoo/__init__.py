"""gluon.model_zoo (ref: python/mxnet/gluon/model_zoo/; bert mirrors the
GluonNLP model family named by BASELINE.json)."""
from . import vision
from . import bert
from . import ssd
from . import language_model
from . import causal_lm
from . import sambay
from . import moe_decoder
from .vision import get_model
