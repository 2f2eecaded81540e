"""The program's names that the trace reduction looks for, kept in one
place.  Each is matched as a substring of a device event's name (program
or operation) in the profiler trace."""

#: The streaming chunk program (``controller._fleet_stream_chunk_jit``).
CHUNK_PROGRAM = "_fleet_stream_chunk_jit"
#: The table-build program (``controller._fleet_dvfs_tables_jit``).
TABLES_PROGRAM = "_fleet_dvfs_tables_jit"
#: The Pallas grid-argmin kernel (``kernels/grid_argmin/kernel.py``): the
#: TPU trace names its operation after the ``pallas_call`` (``%grid_argmin.N``).
GRID_ARGMIN_KERNEL = "%grid_argmin."
#: The benchmark's own span around each entry call.
CALL_SPAN = "bench.call"
