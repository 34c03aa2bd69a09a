"""`depthwise_roofline` (layer: kernels, depthwise 3-D conv). As
`conv_roofline`, for the depthwise layers (`conv_b` of every X3D block and the
stem's temporal `stem_t`)."""

from benchmarks.lib import roofline

SCOPE = r"/(conv_b|stem_t)/"


def read(results):
    return roofline.class_share(results, "conv_depthwise", SCOPE)
