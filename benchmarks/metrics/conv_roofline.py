"""`conv_roofline` (layer: kernels, dense and pointwise 3-D conv). Least time
for the dense and pointwise convs' work of one step (forward, data-gradient
and weight-gradient convs of the plain reference, each at
max(flops/peak, bytes/bandwidth)) over the device time per step of the ops
under those layers' module scopes. The scope, not the op's kind, selects the
time: a conv, a conv+BN fusion and a Pallas call under `.../conv/` all count."""

from benchmarks.lib import roofline

# flax scopes of the dense and pointwise conv layers in models/slowfast.py,
# models/x3d.py, models/common.py
SCOPE = r"/(conv|stem_xy|head_conv|fc1|fc2)/[^/]*conv_general_dilated|/(conv|stem_xy|head_conv)/"


def read(results):
    return roofline.class_share(results, "conv_dense", SCOPE)
