from .cli import main

if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
