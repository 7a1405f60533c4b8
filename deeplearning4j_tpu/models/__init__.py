from deeplearning4j_tpu.models.zoo import ZooModel  # noqa: F401
from deeplearning4j_tpu.models.lenet import LeNet  # noqa: F401
from deeplearning4j_tpu.models.simplecnn import SimpleCNN  # noqa: F401
from deeplearning4j_tpu.models.alexnet import AlexNet  # noqa: F401
from deeplearning4j_tpu.models.vgg import VGG16, VGG19  # noqa: F401
from deeplearning4j_tpu.models.resnet50 import ResNet50  # noqa: F401
from deeplearning4j_tpu.models.darknet import Darknet19, TinyYOLO  # noqa: F401
from deeplearning4j_tpu.models.textgenlstm import TextGenerationLSTM  # noqa: F401
from deeplearning4j_tpu.models.googlenet import GoogLeNet  # noqa: F401
from deeplearning4j_tpu.models.facenet import InceptionResNetV1, FaceNetNN4Small2  # noqa: F401
from deeplearning4j_tpu.models.kimi_linear import KimiLinear  # noqa: F401
from deeplearning4j_tpu.models.qwen3_next import Qwen3Next  # noqa: F401
from deeplearning4j_tpu.models.ouro import Ouro  # noqa: F401
from deeplearning4j_tpu.models.mellum import Mellum2  # noqa: F401
from deeplearning4j_tpu.models.joyai import JoyAIFlash  # noqa: F401
from deeplearning4j_tpu.models.lfm2 import Lfm2Moe  # noqa: F401
from deeplearning4j_tpu.models.granite_hybrid import GraniteHybrid  # noqa: F401
from deeplearning4j_tpu.models.phi4_flash import Phi4Flash  # noqa: F401
