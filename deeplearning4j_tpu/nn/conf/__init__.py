from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: F401
from deeplearning4j_tpu.nn.conf.network import (  # noqa: F401
    NeuralNetConfiguration,
    MultiLayerConfiguration,
)

# import layer modules for their registry side effects (JSON serde)
from deeplearning4j_tpu.nn.conf import convolutional as _conv  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import normalization as _norm  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import pooling as _pool  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import recurrent as _rnn  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import objdetect as _objdetect  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import pretrain as _pretrain  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import variational as _vae  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import regularization as _reg  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import attention as _attn  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import short_conv as _sconv  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import linear_attention as _linattn  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import state_space as _ssm  # noqa: F401,E402
from deeplearning4j_tpu.nn.conf import experts as _experts  # noqa: F401,E402
