"""Process/device runtime — TPU-native equivalent of the reference's L0 layer."""

from .dist import (  # noqa: F401
    CACHE_DIR_ENV,
    COMPILE_CACHE_ENV,
    DistContext,
    cleanup_distributed,
    compile_cache_dir,
    compile_cache_mode,
    cpu_requested,
    enable_persistent_compile_cache,
    is_distributed,
    per_process_seed,
    require_backend,
    set_seed,
    setup_distributed,
)
