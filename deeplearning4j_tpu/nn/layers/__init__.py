from .base import LayerConf
from .core import (ActivationLayer, AutoEncoder, CenterLossOutputLayer,
                   DenseLayer, DropoutLayer, EmbeddingLayer,
                   EmbeddingSequenceLayer, LossLayer,
                   PositionalEmbeddingLayer,
                   OutputLayer, RnnOutputLayer)
from .conv import (Convolution1DLayer, ConvolutionLayer, GlobalPoolingLayer,
                   SubsamplingLayer, Subsampling1DLayer, ZeroPadding1DLayer,
                   ZeroPaddingLayer)
from .norm import (BatchNormalization, LayerNormalization,
                   LocalResponseNormalization, RMSNorm)
from .gated import GatedMLP, GatedShortConvLayer, MixtureOfExpertsLayer
from .attention import (LatentAttentionLayer, LightningAttentionLayer,
                        SelfAttentionLayer)
from .recurrent import (GravesBidirectionalLSTM, GravesLSTM, LSTM,
                        LastTimeStepLayer)
from .variational import (BernoulliReconstructionDistribution,
                          CompositeReconstructionDistribution,
                          ExponentialReconstructionDistribution,
                          GaussianReconstructionDistribution,
                          LossFunctionWrapper, RBM, VariationalAutoencoder)

__all__ = [
    "SelfAttentionLayer", "LatentAttentionLayer",
    "LightningAttentionLayer",
    "BernoulliReconstructionDistribution", "CompositeReconstructionDistribution",
    "ExponentialReconstructionDistribution", "GaussianReconstructionDistribution",
    "LossFunctionWrapper", "RBM", "VariationalAutoencoder",
    "LayerConf", "ActivationLayer", "AutoEncoder", "CenterLossOutputLayer",
    "DenseLayer", "DropoutLayer", "EmbeddingLayer", "EmbeddingSequenceLayer",
    "LossLayer", "OutputLayer",
    "PositionalEmbeddingLayer",
    "RnnOutputLayer", "Convolution1DLayer", "ConvolutionLayer",
    "GlobalPoolingLayer", "SubsamplingLayer", "Subsampling1DLayer",
    "ZeroPadding1DLayer", "ZeroPaddingLayer", "BatchNormalization",
    "LayerNormalization", "RMSNorm",
    "GatedMLP", "GatedShortConvLayer", "MixtureOfExpertsLayer",
    "LocalResponseNormalization",
    "GravesBidirectionalLSTM", "GravesLSTM", "LSTM", "LastTimeStepLayer",
]
