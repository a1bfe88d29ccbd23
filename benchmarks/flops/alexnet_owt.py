"""Model FLOPs of ``alexnet_owt`` from its shapes."""

from benchmarks.flops import jaxpr_count
from benchmarks.reference import alexnet_owt as ref

# (layer, kernel HWIO or (in, out)): Krizhevsky 2014's sizes as alexnet.cc
# has them; 256 x 6 x 6 features reach the first fully connected layer
LAYERS = [("conv1", (11, 11, 3, 64)), ("conv2", (5, 5, 64, 192)),
          ("conv3", (3, 3, 192, 384)), ("conv4", (3, 3, 384, 256)),
          ("conv5", (3, 3, 256, 256)), ("lienar1", (9216, 4096)),
          ("linear2", (4096, 4096)), ("linear3", (4096, 1000))]


def train_flops_per_item(config, mix):
    return jaxpr_count.cnn_train_flops_per_item(ref.forward, LAYERS, config,
                                                published=(224, 1000))
