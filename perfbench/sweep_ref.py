"""``sweep.py`` for a cell whose mix names another runner than
``serve``: the same loop, the same limits, the same lines.

    python3 perfbench/sweep_ref.py --workload <cell> --rates 0.5,1,1.5

``sweep.py`` loads ``runners/serve.py`` by name and calls its ``setup``,
``run_window`` and ``reduce_window``; here the one load of that path is
answered with the mix's own runner, which exports the three.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [os.path.dirname(HERE)] + [
    p for p in sys.path if os.path.abspath(p or '.') != HERE]

from perfbench import run as run_mod  # noqa: E402
from perfbench import sweep  # noqa: E402


def main() -> int:
    workload = sys.argv[sys.argv.index('--workload') + 1]
    mix = run_mod.find_cell(workload)[3]
    load = run_mod.load_module
    serve_py = os.path.join(HERE, 'runners', 'serve.py')

    def load_runner(path):
        if os.path.abspath(path) == serve_py:
            path = os.path.join(HERE, 'runners', mix['runner'] + '.py')
        return load(path)

    run_mod.load_module = load_runner
    return sweep.main()


if __name__ == '__main__':
    sys.exit(main())
