"""kernel.stream_stored_bytes_per_inf.reactnet_a: bytes the dispatched
vote programs store for ReActNet-A's residual stream, each layer at its
own width (int32 throughout at this configuration's bound; a doubling
1x1 at its full width), per row they answered, from the program's
counters `kernel.stream_stored_bytes` and `kernel.rows` (`repro.obs`).
It is read as Bi-Real-18's is
(`kernel.stream_stored_bytes_per_inf.bireal18.py`): the counters run from
process start and every call of the offline cell fills its bucket, so
the stream's rows are the rows answered; read in the traced run; None
where the program keeps no such counter.  20,170,752 B at published
widths (5,042,688 elements at 4 B)."""

import importlib.util
from pathlib import Path

_PATH = Path(__file__).with_name(
    "kernel.stream_stored_bytes_per_inf.bireal18.py")
_SPEC = importlib.util.spec_from_file_location("bench_stored_bireal18",
                                               _PATH)
_MOD = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(_MOD)
read = _MOD.read
