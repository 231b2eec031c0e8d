from locust_tpu.config import compile_cache_dir

compile_cache_dir()  # before the first `import jax` (cli imports it lazily)

from locust_tpu.cli import main  # noqa: E402

raise SystemExit(main())
