from .fuzzer import Fuzzer, FuzzerWeights, MessageGenerator
from .program import FuzzProgram

__all__ = ["Fuzzer", "FuzzerWeights", "FuzzProgram", "MessageGenerator"]
