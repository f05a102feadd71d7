"""repro — KPynq (work-efficient triangle-inequality K-means) rebuilt as
a multi-pod JAX/TPU framework. See README.md and docs/."""

__version__ = "1.0.0"
