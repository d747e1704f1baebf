"""Wall-modelled channel LES at Re_tau ~ 2000 (Hoyas & Jimenez 2006's
Re_tau = 2003 DNS): the WMLES box 2 pi h x 2h x pi h, matching height
h / Ky = 0.125 h (y+ = 250, the log layer; Kawai & Larsson 2012), at the
paper's N = 5 on 8 x 8 x 4 elements (55,296 nodes per env).

u_tau and nu make the Reichardt profile's bulk equal u_b = 1 (h = 1):
Ub+ = 21.70.  18 fixed-point rounds invert the wall law at y+ = 250 to
4e-7 relative.  `use_kernels` stays None (auto), as in relexi_hit.
"""
import math

from ..cfd.channel import ChannelConfig

RE2000 = ChannelConfig(n_poly=5, n_elem=(8, 8, 4),
                       lengths=(2.0 * math.pi, 2.0, math.pi),
                       u_tau=0.04608, nu=2.304e-5, wm_iters=18,
                       dt_rl=0.05, t_end=1.0)
