"""Share of decoded-image lookups that missed the program's decode cache
over the whole run (`data/wire.get_decode_cache().stats()`): the records
stand for a dataset far larger than the cache, so this has to stay near
100. Nothing to read where the cache is off."""


def read(run):
    hits = run.counters.get("decode_cache.hits")
    misses = run.counters.get("decode_cache.misses")
    if hits is None or not hits + misses:
        return None
    return 100.0 * misses / (hits + misses)
