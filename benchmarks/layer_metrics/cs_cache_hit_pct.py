"""Hit share of the chunkservers' block cache (Python LRU + the native
engine's): ``Stats`` hits over hits + misses, delta over the window, all
chunkservers."""


def read(win):
    hits, misses = win.delta("cs.cache_hits"), win.delta("cs.cache_misses")
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
