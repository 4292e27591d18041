from acg_tpu.sparse.csr import CsrMatrix, coo_to_csr
from acg_tpu.sparse.ell import EllMatrix
from acg_tpu.sparse.poisson import (poisson2d_5pt, poisson3d_7pt,
                                    poisson3d_7pt_dia,
                                    poisson3d_7pt_varcoef, poisson3d_27pt,
                                    poisson3d_27pt_dia, random_spd)
