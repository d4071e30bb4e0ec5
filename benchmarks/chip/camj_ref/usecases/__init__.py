"""Ed-Gaze and Rhythmic structures (copied with the reference model)."""
from .edgaze import EDGAZE_VARIANTS, build_edgaze
from .rhythmic import RHYTHMIC_VARIANTS, build_rhythmic

#: algorithm name -> (build function, variants in sweep order)
ALGORITHMS = {"edgaze": (build_edgaze, EDGAZE_VARIANTS),
              "rhythmic": (build_rhythmic, RHYTHMIC_VARIANTS)}
