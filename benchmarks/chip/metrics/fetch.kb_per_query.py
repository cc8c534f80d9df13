"""fetch.kb_per_query: see readers.fetch_kb_per_query."""
from readers import fetch_kb_per_query as read  # noqa: F401
