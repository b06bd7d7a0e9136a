"""gluon.model_zoo (parity: python/mxnet/gluon/model_zoo/)."""
from . import vision
from . import bert
from . import dlrm as dlrm_zoo
from .vision import get_model
from .bert import (BERTModel, BERTForPretraining, bert_base, bert_large,
                   shard_for_tensor_parallel)
from .dlrm import DLRM, dlrm_tiny
from .moe_lm import DecoderLM, MoEDecoderLM
from .mla_lm import MLADecoderLM
