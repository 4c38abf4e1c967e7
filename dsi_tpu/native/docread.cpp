// Whole small files, and their lengths, for utils/ioread.ReadAheadDocs.
//
// A thread of the index job hands a run of documents over at once:
// their names and the descriptor of each one's directory (or -1: the
// name is a path).  No call goes through the interpreter: a Python
// thread pays for its lock after every one of them, which on a host
// with slow file calls and a busy dispatch loop is most of what reading
// costs (PERF.md §5).

#include <cerrno>
#include <cstdint>
#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

extern "C" {

// The lengths the job begins from, one call a file (fstatat), named as
// above.  Returns -1, or the index of the first file that has none, with
// ``*err`` its errno.
long doc_file_lengths(const char* const* names, const int* dir_fds, long n,
                      int64_t* lengths, int* err) {
  *err = 0;
  for (long i = 0; i < n; i++) {
    struct stat st;
    if (fstatat(dir_fds[i] < 0 ? AT_FDCWD : dir_fds[i], names[i], &st, 0)) {
      *err = errno;
      return i;
    }
    lengths[i] = (int64_t)st.st_size;
  }
  return -1;
}

// The files' bytes, given the length each had when the job began: three
// calls a file (openat, one read of its length and a byte, close).  The
// bytes land one file behind the other in ``out`` (sum of the lengths,
// and one byte more).  Returns -1 when every file was read and was its
// length; else the index of the first that was not, with ``*err`` its
// errno, or 0 where the file was read and was another length (it grew
// or was cut since).
long doc_read_files(const char* const* names, const int* dir_fds,
                    const int64_t* lengths, long n, uint8_t* out,
                    int* err) {
  *err = 0;
  for (long i = 0; i < n; i++) {
    int fd = openat(dir_fds[i] < 0 ? AT_FDCWD : dir_fds[i], names[i],
                    O_RDONLY | O_CLOEXEC);
    if (fd < 0) {
      *err = errno;
      return i;
    }
    // One byte more than the length, into the next file's place (it is
    // read after this one): a file that grew shows it, and one that did
    // not is at its end with this one call.
    int64_t want = lengths[i], got = 0;
    int failed = 0;
    while (got <= want) {
      ssize_t r = read(fd, out + got, (size_t)(want + 1 - got));
      if (r < 0) {
        if (errno == EINTR) continue;
        failed = errno;
        break;
      }
      if (r == 0) break;
      got += r;
      if (got == want) break;  // asked for more, and there was no more
    }
    close(fd);
    if (failed || got != want) {
      *err = failed;
      return i;
    }
    out += want;
  }
  return -1;
}

}  // extern "C"
