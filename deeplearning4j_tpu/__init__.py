"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A ground-up rebuild of the Deeplearning4j ecosystem's capabilities
(reference: ``hilo1988/deeplearning4j``: ND4J ndarray + SameDiff autodiff +
DL4J ``MultiLayerNetwork``/``ComputationGraph`` + DataVec ETL + distributed
training) designed TPU-first on JAX/XLA:

- ndarray + op layer   -> :mod:`deeplearning4j_tpu.ndarray`, :mod:`deeplearning4j_tpu.ops`
  (reference: nd4j ``org.nd4j.linalg.api.ndarray.INDArray`` / ``Nd4j``)
- autodiff graph layer -> :mod:`deeplearning4j_tpu.autodiff`
  (reference: ``org.nd4j.autodiff.samediff.SameDiff``)
- NN API               -> :mod:`deeplearning4j_tpu.nn`
  (reference: ``org.deeplearning4j.nn.multilayer.MultiLayerNetwork``,
  ``org.deeplearning4j.nn.graph.ComputationGraph``)
- data/ETL             -> :mod:`deeplearning4j_tpu.datasets`, :mod:`deeplearning4j_tpu.datavec`
- distributed          -> :mod:`deeplearning4j_tpu.parallel`
  (reference: ``ParallelWrapper`` / Spark ``SharedTrainingMaster`` -> XLA
  collectives over ICI/DCN via jax.sharding)
- model zoo            -> :mod:`deeplearning4j_tpu.models`

Design stance (SURVEY.md section 7): functional core with a mutable facade.
All compute compiles through XLA; there are no hand-written kernels except
Pallas where XLA underperforms. Memory is XLA-owned (donation instead of
workspaces); updaters are pure functions over optimizer-state pytrees.
"""

__version__ = "0.1.0"

from deeplearning4j_tpu.common import compilecache as _compilecache
from deeplearning4j_tpu.common.dtypes import DataType  # noqa: F401

# the one place every entry path passes, before the process compiles
# anything: decide where the persistent XLA compilation cache lives
_compilecache.configure()
