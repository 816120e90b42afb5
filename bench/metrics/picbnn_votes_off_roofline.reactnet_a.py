"""picbnn_votes_off_roofline.reactnet_a: the roofline's least time of the
window's work of the vote program `jit_picbnn_votes_off` over the device
time of that program in the traced window, in %, for ReActNet-A.  The
net runs as that one XLA program (int8 convolutions on the MXU, the
int32 residual stream, RSign, RPReLU and the shortcuts fused between
them), so no Pallas kernel call measures it, and its fusions outnumber
the ten top ops the trace keeps.  It is read as Bi-Real-18's is
(`picbnn_votes_off_roofline.bireal18.py`): the program's time is the
device's busy time less the top ops of any other program; the work is
the rows answered, with the configuration's operations and bytes
(`bench/configs/reactnet_a_imagenet.py`: the stream is not counted as
work), the weights read once per call.  None where the trace's top ops
hold no op of that program."""

import importlib.util
from pathlib import Path

_PATH = Path(__file__).with_name("picbnn_votes_off_roofline.bireal18.py")
_SPEC = importlib.util.spec_from_file_location("bench_roofline_bireal18",
                                               _PATH)
_MOD = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(_MOD)
read = _MOD.read
