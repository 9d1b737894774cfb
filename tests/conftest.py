"""Test env: force CPU backend with 8 virtual devices so mesh/sharding
tests run anywhere (reference TestDistBase spawns localhost subprocesses
instead — see SURVEY.md §4.4)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The suite's thousands of CPU executables stay out of the checkout's
# own cache (<repo>/.jax_cache — that directory rides to the chip with
# every copy): the tests keep theirs in one fixed directory under the
# user's cache home, warm from one run to the next. Test subprocesses
# inherit it.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu", "xla"))

# numeric tests compare against float64 numpy oracles; keep matmuls at
# full precision here (TPU bench runs keep the fast bf16 default)
import jax

jax.config.update("jax_default_matmul_precision", "highest")



def alloc_free_ports(n):
    """Kernel-assigned free localhost ports for PS tests (shared
    allocator — hand-picked bases collided across test files)."""
    import socket

    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return [f"127.0.0.1:{p}" for p in ports]
