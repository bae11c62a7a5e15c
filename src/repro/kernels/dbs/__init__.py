"""The unified DBS kernel package: ``dbs_copy`` + ``dbs_rw`` behind one ops
surface and a registry (docs/KERNELS.md). ``repro.kernels.dbs_copy`` is the
deprecation shim over this package."""
from repro.kernels.dbs.ops import (dbs_copy, dbs_copy_pool,  # noqa: F401
                                   dbs_copy_reference, dbs_read_bytes,
                                   dbs_rw_read_pool, dbs_rw_write_pool,
                                   dbs_rw_write_rows, dbs_write_bytes,
                                   default_interpret)
from repro.kernels.dbs.ref import (dbs_copy_ref, dbs_rw_read_ref,  # noqa: F401
                                   dbs_rw_write_ref)
from repro.kernels.dbs.registry import (DBSKernel,  # noqa: F401
                                        available_kernels, make_kernel,
                                        register_kernel, resolve_kernel_name)
from repro.kernels.dbs.rw_kernel import dbs_rw_read, dbs_rw_write  # noqa: F401
