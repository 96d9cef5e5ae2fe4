"""Layer: kernels.  Share of the roofline over the Compressed Convolutional
Attention stages (norm, the latents' projection, the depthwise and the
grouped causal convolution, value shift, query-key mean, the heads'
normalisation, rotary embedding, causal grouped-query attention inside the
latent, the up-projection and the scaled residual add, forward and backward
with what the step rematerialises), whichever tier implements them: work
from the stages' shapes, time from every device event under the stages'
scopes."""
from benchmark import roofline


def read(facts):
    return roofline.kind_share(facts, "cca")
