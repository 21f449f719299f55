import os
import sys

# tests run single-device (the dry-run owns the 512-device trick);
# distributed tests spawn subprocesses with their own XLA_FLAGS.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str, ndev: int = 8, x64: bool = False, timeout=420):
    """Run a code snippet in a subprocess with its own XLA device count
    (the main pytest process stays single-device)."""
    import subprocess
    import sys
    import textwrap

    from repro.jaxenv import host_mesh_env
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=host_mesh_env(ndev, x64=x64),
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout
