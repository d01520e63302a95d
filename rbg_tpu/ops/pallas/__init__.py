"""The Pallas kernels of the serving path, and the one choice between a
kernel and its XLA form.

Every operation that has a kernel (the page walks of ``ops/paged_attention``,
``ops/ragged_paged_attention`` and ``ops/mla_attention``, the delta rule's
decode step in ``ops/kda``, a decode step's visits to its hit experts in
``models/llama.py``) asks ``dispatch_pallas`` by the kernel's name; ``KERNELS``
says which module of this package holds it. The modules are imported when a
kernel is first asked for, so a process that never takes one never loads
Mosaic's lowering.

Off a TPU a kernel runs only in interpret mode: tests wrap every entry of
``KERNELS`` at once (``tests/conftest.py::interpreted``), where ``kernel``
finds the wrapped function because it reads the module's attribute at each
call.
"""

import importlib

import jax

# the kernel's entry point -> the module of this package that defines it
KERNELS = {
    "paged_attention_pallas": "paged_attention_kernel",
    "paged_attention_pallas_q": "paged_attention_kernel",
    "paged_mla_attention_pallas": "paged_attention_kernel",
    "paged_mla_attention_pallas_q": "paged_attention_kernel",
    "ragged_paged_attention_pallas": "ragged_attention_kernel",
    "ragged_paged_attention_pallas_q": "ragged_attention_kernel",
    "ragged_paged_mla_attention_pallas": "ragged_attention_kernel",
    "ragged_paged_mla_attention_pallas_q": "ragged_attention_kernel",
    "kda_decode_pallas": "kda_kernel",
    "moe_visit_pallas": "moe_visit_kernel",
}


def home(kernel_name: str):
    """The module that defines ``kernel_name``."""
    return importlib.import_module(f"{__name__}.{KERNELS[kernel_name]}")


def kernel(kernel_name: str):
    return getattr(home(kernel_name), kernel_name)


def takes_kernel(use_pallas: str) -> bool:
    """``dispatch_pallas``'s policy alone: whether this process runs the
    kernels."""
    return use_pallas == "always" or (use_pallas == "auto"
                                      and jax.default_backend() == "tpu")


def dispatch_pallas(use_pallas: str, kernel_name: str, xla_fn, args, **kw):
    """The ONE kernel-vs-XLA dispatch policy (the page walks, the delta
    rule's decode step and the experts' visits all use it):
    'always' takes the kernel everywhere, 'auto' takes it on a TPU and
    the XLA path on any other backend, 'never' the XLA path. A kernel
    that cannot be imported is an error, never a reason to run XLA.
    ``kw``: what both forms take by name (a window layer's ``window``)."""
    if takes_kernel(use_pallas):
        return kernel(kernel_name)(*args, **kw)
    return xla_fn(*args, **kw)
