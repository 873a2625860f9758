"""Entry point: ``PYTHONPATH=src python -m repro.api`` (see cli.py)."""

from repro.compile_cache import enable_compile_cache

from .cli import main

if __name__ == "__main__":
    enable_compile_cache()
    try:
        raise SystemExit(main())
    except BrokenPipeError:  # stdout piped into head/less that exited
        raise SystemExit(0)
