"""spark_tpu: a TPU-native analytics engine with Spark SQL's capabilities.

See SURVEY.md for the blueprint (reference: apache/spark 3.3.0-SNAPSHOT)
and README.md for the architecture stance: Catalyst-shaped compiler,
columnar jax.Array batches, XLA as the whole-stage codegen, collectives
as the shuffle.
"""

import os

import jax

# The engine operates on 64-bit SQL types (BIGINT, DOUBLE, scaled-int64
# decimals); enable them globally before any array is created.
jax.config.update("jax_enable_x64", True)

#: the checkout this package sits in. Cache directories resolve against
#: it, never against the working directory: JAX's persistent cache only
#: hits when every process looks in the same place.
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The ONE place the engine places JAX's persistent compilation cache:
# an operator's JAX_COMPILATION_CACHE_DIR is JAX's to read, otherwise
# it sits in the checkout (git-ignored), and nothing else in the
# package sets a directory. Without the threshold JAX keeps only
# programs that took over a second to compile, and most stage programs
# of a query do not.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CHECKOUT, ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

from . import functions  # noqa: E402
from . import types  # noqa: E402
from .columnar import Batch, Column  # noqa: E402
from .config import Conf  # noqa: E402
from .dataframe import DataFrame  # noqa: E402
from .session import SparkTpuSession  # noqa: E402

__version__ = "0.1.0"

__all__ = ["SparkTpuSession", "DataFrame", "Batch", "Column", "Conf",
           "functions", "types", "__version__"]
