"""``python -m repro.experiment`` — alias for ``python -m repro.experiment.cli``."""

from repro.experiment.cli import main
from repro.launch.bootstrap import setup_compile_cache

if __name__ == "__main__":
    setup_compile_cache()
    main()
