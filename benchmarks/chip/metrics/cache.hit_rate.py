"""cache.hit_rate: see readers.cache_hit_rate."""
from readers import cache_hit_rate as read  # noqa: F401
