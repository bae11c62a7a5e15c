"""Deprecation shim: the ops surface lives in ``repro.kernels.dbs.ops``."""
from repro.kernels.dbs.ops import (dbs_copy, dbs_copy_pool,  # noqa: F401
                                   dbs_copy_reference, default_interpret)
