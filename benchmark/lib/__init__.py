"""The yardstick: traffic generation, arithmetic and reductions. No JAX is
imported by any module here except ``xplane`` and ``check``, which run in
the server process."""
