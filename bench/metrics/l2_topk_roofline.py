"""Share (%) of its roofline that the ``l2_topk`` scan kernel reached
(``roofline.kernel_share``): operations or bytes of its calls from their
shapes, over the device time of its program in the trace."""

import roofline


def read(run):
    return roofline.kernel_share(run, "l2_topk", "jit_l2_topk_pallas")
