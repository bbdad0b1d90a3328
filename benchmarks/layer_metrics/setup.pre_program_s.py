"""setup.pre_program_s (s): the process's start (`/proc/self/stat`) to the program's first set-up stage: the interpreter, the harness's own imports, `import jax` and the TPU client coming up, which `harness.run` does before the program is imported. The program does not own it; the ledger makes it visible."""

from lib.setup_ledger import pre_program_s


def read(obs):
    return pre_program_s()
