"""Work of the fixed-radius Hamming scan, whatever implements it.

Each (query, row) pair compares two `bits`-bit signatures. Counted in the
+-1 int8 matmul form (distance = (bits - <q, x>) / 2): 2 * bits operations
a pair at the int8 peak, the form a matrix-unit scan would take, so that
neither it nor a bit-packed vector scan can read above its least time.
Bytes: each scanned row's packed signature and its tombstone-mask byte,
read once, plus the queries' signatures. A pruned scan needs only the
rows of the blocks it admits, so `rows` is the rows scanned per query.
"""


def work(queries: float, rows: float, bits: int = 256) -> tuple:
    """-> (int8 operations, bytes) of scanning `rows` rows per query."""
    ops = 2.0 * queries * rows * bits
    nbytes = rows * (bits // 8 + 1) + queries * bits // 8
    return ops, nbytes


def least_seconds(queries: float, rows: float, bits: int, peaks: dict):
    """-> (least seconds on the chip, which bound sets it)."""
    ops, nbytes = work(queries, rows, bits)
    compute = ops / peaks["int8_ops"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return max(compute, memory), ("compute" if compute >= memory else "memory")
