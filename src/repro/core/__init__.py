"""KPynq core: work-efficient triangle-inequality K-means in JAX."""
from .api import KMeans, NotFittedError
from .distances import pairwise_dists, pairwise_sq_dists, rowwise_dists
from .distributed import distributed_yinyang, make_mesh
from .engine import EngineConfig, EngineStats, fit as engine_fit
from .init import kmeans_plusplus, random_init
from .kmeans import EvalCount, KMeansResult, group_centroids, lloyd, yinyang

__all__ = [
    "KMeans", "KMeansResult", "NotFittedError", "lloyd", "yinyang",
    "group_centroids", "kmeans_plusplus", "random_init",
    "distributed_yinyang", "make_mesh",
    "engine_fit", "EngineStats",
    "EngineConfig", "EvalCount",
    "pairwise_dists", "pairwise_sq_dists", "rowwise_dists",
]
