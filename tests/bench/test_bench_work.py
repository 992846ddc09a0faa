"""The roofline work functions against bytes counted by hand."""

from bench.harness import work


def test_hamming_rows_bytes_by_hand():
    # 2 queries, 3 candidates each, d = 40 -> 2 words of sketch bits.
    # query sketches 2*2*4 = 16 B, candidate sketches 2*3*2*4 = 48 B,
    # distances 2*3*4 = 24 B.
    assert work.hamming_rows_bytes(2, 3, 40) == 16 + 48 + 24


def test_qdist_windows_bytes_by_hand():
    # 2 queries, k2 = 3, h = 1 -> 9 candidates each; d = 16 at 4 bits ->
    # 2 words.  queries 2*16*4 = 128 B, codes 2*9*2*4 = 144 B, centroids
    # 16*16*4 = 1024 B, distances 2*9*4 = 72 B.
    assert work.qdist_windows_bytes(2, 3, 1, 16, 16) == 128 + 144 + 1024 + 72


def test_table1_row1_sizes():
    # One 512-query chunk at Table-1 row 1, d = 384 (12 sketch words, 48
    # code words, 1,850 stage-2 candidates): 37.8 MB per tree for stage 1
    # and 186 MB for stage 2.
    assert work.hamming_rows_bytes(512, 1420, 384) == (
        512 * 12 * 4 + 512 * 1420 * 12 * 4 + 512 * 1420 * 4) == 37_830_656
    assert work.qdist_windows_bytes(512, 370, 2, 384, 16) == (
        512 * 384 * 4 + 512 * 1850 * 48 * 4 + 384 * 16 * 4
        + 512 * 1850 * 4) == 186_462_208
