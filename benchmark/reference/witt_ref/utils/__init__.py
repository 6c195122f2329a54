from .javarand import JavaRandom
from .gpd import GeneralizedParetoDistribution
from .more_math import log2, round_pow2

__all__ = ["JavaRandom", "GeneralizedParetoDistribution", "log2", "round_pow2"]
