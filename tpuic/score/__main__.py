"""``python -m tpuic.score`` — the elastic bulk-scoring worker CLI."""

import sys

from tpuic.compiled.cache import enable_compile_cache
from tpuic.score.driver import main

if __name__ == "__main__":
    enable_compile_cache()
    sys.exit(main())
