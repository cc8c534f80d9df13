"""stage1_roofline: see readers.stage1_roofline."""
from readers import stage1_roofline as read  # noqa: F401
