from repro.kernels.hamming.ops import (  # noqa: F401
    gather_sketches,
    hamming_matrix,
    hamming_rows,
    hamming_rows_word_major,
)
from repro.kernels.hamming.ref import hamming_matrix_ref  # noqa: F401
