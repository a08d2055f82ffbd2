"""``python -m repro.gym`` -> the gym CLI."""

import sys

from repro.gym.cli import main
from repro.launch.bootstrap import setup_compile_cache

setup_compile_cache()
main(sys.argv[1:])
