"""The benchmark: cells, traffic, reference, trace reduction, metric readers.

Everything here is the yardstick. The program under test is the package
``distributed_pytorch_training_tpu`` (and ``train.py``'s attention rule);
nothing in this directory is imported by it.
"""
