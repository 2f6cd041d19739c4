"""Test infrastructure (reference testkit/ module, SURVEY §2.16, §4)."""
from .builder import TestFeatureBuilder
from .generators import (
    RandomBinary, RandomIntegral, RandomList, RandomMap, RandomPickList,
    RandomReal, RandomSet, RandomText, RandomVector, planted_linear_frame,
)

__all__ = ["TestFeatureBuilder", "RandomReal", "RandomIntegral",
           "RandomBinary", "RandomText", "RandomPickList", "RandomList",
           "RandomSet", "RandomMap", "RandomVector",
           "planted_linear_frame"]
