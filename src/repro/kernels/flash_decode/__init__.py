from repro.kernels.flash_decode.flash_decode import pages_walked  # noqa: F401
from repro.kernels.flash_decode.ops import flash_decode  # noqa: F401
from repro.kernels.flash_decode.ref import flash_decode_ref  # noqa: F401
