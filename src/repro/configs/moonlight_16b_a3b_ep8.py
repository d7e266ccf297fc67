"""moonlight-16b-a3b-ep8: one chip's share of Moonlight-16B-A3B served
expert-parallel over 8 chips.  Each MoE layer's routed experts are split 8
ways and this chip holds the first share (experts 0-7 of 64; 0-1 of 16 at
the smoke size).  The router keeps its full width and top-6, so tokens
routed to experts held elsewhere add nothing here; attention, the dense
layer, the shared experts, the embedding and the head are whole
(data-parallel attention).  Depth, widths and vocabulary are as published.
"""

import dataclasses

from repro.configs import moonlight_16b_a3b as _full


def _share(cfg):
    m = cfg.moe
    return dataclasses.replace(
        cfg, name=cfg.name.replace("moonlight-16b-a3b", "moonlight-16b-a3b-ep8"),
        moe=dataclasses.replace(m, num_held=m.num_experts // 8))


CONFIG = _share(_full.CONFIG)
SMOKE = _share(_full.SMOKE)
