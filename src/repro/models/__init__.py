"""Model zoo: pattern-based block stacks covering 6 architecture types."""

from .registry import ARCHITECTURES, get_config, get_smoke_config, list_architectures  # noqa: F401
from .transformer import apply_model, init_caches, init_model, init_params  # noqa: F401
