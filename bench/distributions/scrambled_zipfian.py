"""YCSB's ScrambledZipfianGenerator: ranks drawn from a Zipfian over
``YCSB_ITEM_COUNT`` items by Gray et al.'s method, scrambled onto the
``span`` records with FNV-1a (64 bit), so the hot records are spread over
the key space.  The constant is YCSB's ``ZIPFIAN_CONSTANT``, which YCSB's
core workloads do not change.
"""
import numpy as np

ZIPFIAN_CONSTANT = 0.99
YCSB_ITEM_COUNT = 10_000_000_000
FNV_OFFSET, FNV_PRIME = 0xCBF29CE484222325, 1099511628211


def zeta(n: int, theta: float, exact: int = 1 << 20) -> float:
    """sum_{i=1..n} i^-theta: the first ``exact`` terms summed, the rest by
    the Euler-Maclaurin integral (error far below 1e-9 at these sizes)."""
    m = min(n, exact)
    head = float(np.sum(np.arange(1, m + 1, dtype=np.float64) ** -theta))
    if n == m:
        return head
    a, b = float(m), float(n)
    tail = (b ** (1 - theta) - a ** (1 - theta)) / (1 - theta)
    return head + tail + 0.5 * (b ** -theta - a ** -theta)


def zipfian_ranks(rng: np.random.Generator, size: int, items: int,
                  theta: float) -> np.ndarray:
    """Ranks in ``[0, items)`` by Gray et al.'s method, as YCSB's
    ZipfianGenerator draws them: rank 0 is the most popular."""
    zetan = zeta(items, theta)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    tail = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    return np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, tail))


def fnv64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` (FNV-1a over the 8 bytes, then abs)."""
    h = np.full(x.shape, FNV_OFFSET, np.uint64)
    v = x.astype(np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def draw(rng: np.random.Generator, size: int, span: int) -> np.ndarray:
    ranks = zipfian_ranks(rng, size, YCSB_ITEM_COUNT, ZIPFIAN_CONSTANT)
    return fnv64(ranks) % span
