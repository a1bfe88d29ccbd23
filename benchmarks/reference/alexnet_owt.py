"""AlexNet, the "one weird trick" variant (Krizhevsky 2014,
arXiv:1404.5997; the FlexFlow reference's alexnet.cc): five
convolutions, three max pools, three fully connected layers (4096, 4096,
1000), 224x224 input, softmax cross-entropy, mean over the batch.

As the reference's ``alexnet.cc`` builds it, and the configuration file
says so under ``assumed``: the convolutions carry no ReLU and the pools
do (a ReLU after a max pool equals one before it), no LRN, no dropout;
activations are flattened in (h, w, c) order.
"""

from benchmarks.reference import cnn_layers as L


def forward(p, x):
    x = L.conv(p["conv1"], x, (4, 4), (2, 2))
    x = L.max_pool(x, (3, 3), (2, 2), relu=True)
    x = L.conv(p["conv2"], x, (1, 1), (2, 2))
    x = L.max_pool(x, (3, 3), (2, 2), relu=True)
    x = L.conv(p["conv3"], x, (1, 1), (1, 1))
    x = L.conv(p["conv4"], x, (1, 1), (1, 1))
    x = L.conv(p["conv5"], x, (1, 1), (1, 1))
    x = L.max_pool(x, (3, 3), (2, 2), relu=True)
    x = x.reshape(x.shape[0], -1)
    x = L.linear(p["lienar1"], x, relu=True)   # sic: alexnet.cc's name
    x = L.linear(p["linear2"], x, relu=True)
    return L.linear(p["linear3"], x)


def sum_loss_and_grads(params, batch, config):
    return L.sum_loss_and_grads(forward, params, batch)
